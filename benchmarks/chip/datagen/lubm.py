"""LUBM data after the UBA generator's per-department counts.

Guo, Pan and Heflin, "LUBM: A benchmark for OWL knowledge base systems",
J. Web Semantics 3(2), 2005.  The counts (departments per university,
faculty by rank, students per faculty, courses, research groups,
publications, advisors, teaching and research assistants) are read from
the configuration's ``ranges``; each ``[lo, hi]`` is drawn uniformly,
inclusive, as UBA draws them.  Term names follow the repository's Q1-Q14
(``ub:GraduateCourse0.Dept0.Univ0``).

The counts come from a stream of their own, fixed by the configuration's
``count_seed``, so every seed gives the same number of entities of each
kind and the same number of triples: the served graph has the same shapes,
and a second seed finds the first one's programs in the compile cache.  The
seed draws the rest: which courses a student takes, advisors, degree
universities, research interests and assistantships' courses and groups.

The data is loaded as the paper loads LUBM, with what the ontology's OWL
restrictions would infer written out where the RDFS subclass closure cannot
reach it: every department head is typed ``ub:Chair``, and the
``GraduateStudent`` / ``TeachingAssistant`` / ``ResearchAssistant``
classes sit under ``ub:Student`` / ``ub:Person`` in the hierarchy.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.triples import RDF_TYPE, Builder, Dataset

HIERARCHY = [
    ("ub:FullProfessor", "ub:Professor"),
    ("ub:AssociateProfessor", "ub:Professor"),
    ("ub:AssistantProfessor", "ub:Professor"),
    ("ub:Chair", "ub:Professor"),
    ("ub:Professor", "ub:Faculty"),
    ("ub:Lecturer", "ub:Faculty"),
    ("ub:Faculty", "ub:Employee"),
    ("ub:Employee", "ub:Person"),
    ("ub:UndergraduateStudent", "ub:Student"),
    ("ub:GraduateStudent", "ub:Student"),
    ("ub:Student", "ub:Person"),
    ("ub:TeachingAssistant", "ub:Person"),
    ("ub:ResearchAssistant", "ub:Person"),
    ("ub:GraduateCourse", "ub:Course"),
    ("ub:Course", "ub:Work"),
    ("ub:Publication", "ub:Work"),
    ("ub:ResearchGroup", "ub:Organization"),
    ("ub:Department", "ub:Organization"),
    ("ub:University", "ub:Organization"),
]

RANKS = ("FullProfessor", "AssociateProfessor", "AssistantProfessor",
         "Lecturer")


def generate(cfg: dict, seed: int) -> Dataset:
    """The dataset of ``cfg["universities"]`` universities from ``seed``."""
    r = cfg["ranges"]
    n_univ = int(cfg["universities"])
    degree_pool = int(cfg["degree_universities"])
    b = Builder()
    b.subclasses(HIERARCHY)
    P = {name: b.pred("ub:" + name) for name in (
        "name", "emailAddress", "telephone", "researchInterest",
        "undergraduateDegreeFrom", "mastersDegreeFrom", "doctoralDegreeFrom",
        "worksFor", "memberOf", "headOf", "subOrganizationOf", "teacherOf",
        "takesCourse", "advisor", "teachingAssistantOf", "publicationAuthor")}
    typ = b.pred(RDF_TYPE)
    cls = {c: b.shared(f"ub:{c}") for c in (
        *RANKS, "Chair", "UndergraduateStudent", "GraduateStudent",
        "TeachingAssistant", "ResearchAssistant", "Course", "GraduateCourse",
        "ResearchGroup", "Department", "University", "Publication")}
    add, entity, shared = b.add, b.entity, b.shared

    univs = [shared(f"ub:Univ{u}") for u in range(n_univ)]
    for u in range(n_univ):
        rng = np.random.default_rng([seed, u])
        sizes = np.random.default_rng([int(cfg["count_seed"]), u])
        univ = univs[u]
        add(univ, typ, cls["University"])
        add(univ, P["name"], shared(f'"University{u}"'))
        b.population("university", univ)

        def draw(key: str, size=None):
            lo, hi = r[key]
            return sizes.integers(lo, hi + 1, size=size)

        for d in range(int(draw("departments"))):
            tag = f"Dept{d}.Univ{u}"
            dept = shared(f"ub:{tag}")
            add(dept, typ, cls["Department"])
            add(dept, P["subOrganizationOf"], univ)
            add(dept, P["name"], shared(f'"Department{d}"'))
            b.population("department", dept)

            # faculty, by rank
            faculty: list[int] = []
            ranks: list[str] = []
            professors: list[int] = []
            for rank in RANKS:
                for i in range(int(draw(rank))):
                    f = entity(f"ub:{rank}{i}.{tag}")
                    add(f, typ, cls[rank])
                    add(f, P["worksFor"], dept)
                    add(f, P["name"], shared(f'"{rank}{i}"'))
                    add(f, P["emailAddress"], entity(f'"{rank}{i}@{tag}.edu"'))
                    add(f, P["telephone"],
                        entity(f'"{u:03d}-{d:03d}-{len(faculty):04d}"'))
                    add(f, P["researchInterest"], shared(
                        f'"Research{int(rng.integers(r["research_areas"]))}"'))
                    for deg in ("undergraduateDegreeFrom",
                                "mastersDegreeFrom", "doctoralDegreeFrom"):
                        add(f, P[deg], _degree_univ(b, rng, degree_pool,
                                                    univs))
                    faculty.append(f)
                    ranks.append(rank)
                    if rank != "Lecturer":
                        professors.append(f)
                    b.population(rank.lower(), f)
            head = faculty[0]  # FullProfessor0 heads the department
            add(head, typ, cls["Chair"])
            add(head, P["headOf"], dept)

            # courses: each faculty member teaches some of each kind
            courses: list[int] = []
            gcourses: list[int] = []
            for f in faculty:
                for _ in range(int(draw("courses_per_faculty"))):
                    c = entity(f"ub:Course{len(courses)}.{tag}")
                    add(c, typ, cls["Course"])
                    add(c, P["name"], shared(f'"Course{len(courses)}"'))
                    add(f, P["teacherOf"], c)
                    courses.append(c)
                for _ in range(int(draw("graduate_courses_per_faculty"))):
                    c = entity(f"ub:GraduateCourse{len(gcourses)}.{tag}")
                    add(c, typ, cls["GraduateCourse"])
                    add(c, P["name"], shared(
                        f'"GraduateCourse{len(gcourses)}"'))
                    add(f, P["teacherOf"], c)
                    gcourses.append(c)
            for c in courses:
                b.population("course", c)
            for c in gcourses:
                b.population("graduate_course", c)

            groups = []
            for g in range(int(draw("research_groups"))):
                grp = entity(f"ub:ResearchGroup{g}.{tag}")
                add(grp, typ, cls["ResearchGroup"])
                add(grp, P["subOrganizationOf"], dept)
                groups.append(grp)

            n_fac = len(faculty)
            n_ug = n_fac * int(draw("undergraduates_per_faculty"))
            n_gr = n_fac * int(draw("graduates_per_faculty"))
            takes = P["takesCourse"]
            n_courses = len(courses)
            ug_takes = _picks(rng, n_ug, n_courses,
                              draw("undergraduate_courses_taken", n_ug))
            ug_adv = sizes.random(n_ug) < r["undergraduate_advisor_share"]
            ug_adv_of = rng.integers(len(professors), size=n_ug).tolist()
            for i in range(n_ug):
                s = entity(f"ub:UndergraduateStudent{i}.{tag}")
                add(s, typ, cls["UndergraduateStudent"])
                add(s, P["memberOf"], dept)
                add(s, P["name"], shared(f'"UndergraduateStudent{i}"'))
                add(s, P["emailAddress"],
                    entity(f'"UndergraduateStudent{i}@{tag}.edu"'))
                add(s, P["telephone"], entity(f'"{u:03d}-{d:03d}-u{i:05d}"'))
                for c in ug_takes[i]:
                    add(s, takes, courses[c])
                if ug_adv[i]:
                    add(s, P["advisor"], professors[ug_adv_of[i]])
            ta_share = sizes.uniform(*r["graduate_ta_share"])
            ra_share = sizes.uniform(*r["graduate_ra_share"])
            roles = sizes.random(n_gr)
            n_gcourses = len(gcourses)
            g_takes = _picks(rng, n_gr, n_gcourses,
                             draw("graduate_courses_taken", n_gr))
            g_adv_of = rng.integers(len(professors), size=n_gr).tolist()
            grads = []
            for i in range(n_gr):
                s = entity(f"ub:GraduateStudent{i}.{tag}")
                add(s, typ, cls["GraduateStudent"])
                add(s, P["memberOf"], dept)
                add(s, P["name"], shared(f'"GraduateStudent{i}"'))
                add(s, P["emailAddress"],
                    entity(f'"GraduateStudent{i}@{tag}.edu"'))
                add(s, P["telephone"], entity(f'"{u:03d}-{d:03d}-g{i:05d}"'))
                add(s, P["undergraduateDegreeFrom"],
                    _degree_univ(b, rng, degree_pool, univs))
                for c in g_takes[i]:
                    add(s, takes, gcourses[c])
                add(s, P["advisor"], professors[g_adv_of[i]])
                if roles[i] < ta_share:
                    add(s, typ, cls["TeachingAssistant"])
                    add(s, P["teachingAssistantOf"],
                        courses[int(rng.integers(n_courses))])
                elif roles[i] < ta_share + ra_share:
                    add(s, typ, cls["ResearchAssistant"])
                    add(s, P["worksFor"],
                        groups[int(rng.integers(len(groups)))])
                grads.append(s)
                b.population("graduate_student", s)

            # publications: each faculty member's and graduate student's
            # own, counted by rank
            for f, rank in zip(faculty, ranks):
                for j in range(int(draw(f"publications_{rank}"))):
                    pub = entity(f"ub:Publication{j}.{b.terms[f][3:]}")
                    add(pub, typ, cls["Publication"])
                    add(pub, P["name"], shared(f'"Publication{j}"'))
                    add(pub, P["publicationAuthor"], f)
            for s in grads:
                for j in range(int(draw("publications_GraduateStudent"))):
                    pub = entity(f"ub:Publication{j}.{b.terms[s][3:]}")
                    add(pub, typ, cls["Publication"])
                    add(pub, P["name"], shared(f'"Publication{j}"'))
                    add(pub, P["publicationAuthor"], s)
    return b.build()


def _degree_univ(b: Builder, rng, pool: int, univs: list[int]) -> int:
    """A degree's university, uniform over ``pool`` universities; those
    beyond the generated ones are named but hold no data, as in UBA."""
    k = int(rng.integers(pool))
    return univs[k] if k < len(univs) else b.shared(f"ub:Univ{k}")


def _picks(rng, n: int, pool: int, k) -> list[list[int]]:
    """For each of ``n`` students, ``k[i]`` distinct indices below
    ``pool`` (at most ``pool``)."""
    order = rng.random((n, pool)).argsort(axis=1)
    k = np.minimum(k, pool).tolist()
    return [row[:ki] for row, ki in zip(order.tolist(), k)]
