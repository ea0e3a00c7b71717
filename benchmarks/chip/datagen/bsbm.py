"""BSBM data after the BSBM v3 data generator's ratios.

Bizer and Schultz, "The Berlin SPARQL Benchmark", IJSWIS 5(2), 2009.  Per
product: its type, label, comment, producer, features and numeric and
textual properties; ``offers_per_product`` offers (vendor, price, validity,
delivery days, web page) and ``reviews_per_product`` reviews (reviewer,
date, title, text, up to four ratings); producers, vendors and reviewers
in the configured ratios, each with BSBM's properties.  Predicate names
are the repository's B1-B12 (``b:propertyNumeric1``,
``b:reviewerHomepage``); departures from BSBM are the configuration's
``assumed`` list.

As in ``datagen/lubm.py``, the counts come from a stream of their own,
fixed by the configuration's ``count_seed``: how many features each product
has, which of the optional properties 4-6 it has, which ratings each review
has and which reviews carry a reviewer homepage.  Every seed then serves the
same number of triples of each predicate.  The seed draws the rest:
identities, links and values.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.triples import RDF_TYPE, Builder, Dataset


def generate(cfg: dict, seed: int) -> Dataset:
    """The dataset of ``cfg["products"]`` products from ``seed``."""
    r = cfg["ranges"]
    n_prod = int(cfg["products"])
    rng = np.random.default_rng([seed, 0])
    sizes = np.random.default_rng([int(cfg["count_seed"]), 0])
    b = Builder()
    typ = b.pred(RDF_TYPE)
    add, entity, shared = b.add, b.entity, b.shared
    P = {name: b.pred("b:" + name) for name in (
        "label", "comment", "producer", "productFeature", "publisher",
        "publishDate", "product", "vendor", "price", "validFrom", "validTo",
        "deliveryDays", "offerWebpage", "reviewFor", "reviewer", "reviewDate",
        "title", "text", "rating1", "rating2", "rating3", "rating4",
        "reviewerHomepage", "name", "mbox_sha1sum", "country", "homepage",
        *(f"propertyNumeric{i}" for i in range(1, 7)),
        *(f"propertyTextual{i}" for i in range(1, 7)))}

    def count(key: str) -> int:
        return max(1, int(round(n_prod * r[key])))

    n_types = count("product_types_per_product")
    n_feat = count("features_per_product")
    n_producers = count("producers_per_product")
    n_vendors = count("vendors_per_product")
    n_reviewers = count("reviewers_per_product")

    # product type tree: a root, then each type under a random earlier one
    types = [shared("b:ProductType0")]
    b.subclasses([("b:ProductType0", "b:Product")])
    parent = rng.integers(0, np.arange(1, n_types))
    for t in range(1, n_types):
        types.append(shared(f"b:ProductType{t}"))
        b.subclasses([(f"b:ProductType{t}",
                       f"b:ProductType{int(parent[t - 1])}")])
    features = []
    for f in range(n_feat):
        feat = entity(f"b:Feature{f}")
        add(feat, typ, shared("b:ProductFeature"))
        add(feat, P["label"], entity(f'"feature {f}"'))
        features.append(feat)
        b.population("feature", feat)
    countries = [shared(f'"{c}"') for c in cfg["countries"]]
    country_w = np.asarray(cfg["country_weights"], dtype=float)
    country_w /= country_w.sum()

    def agent(kind: str, i: int) -> int:
        a = entity(f"b:{kind}{i}")
        add(a, typ, shared(f"b:{kind}"))
        add(a, P["label"], entity(f'"{kind.lower()} {i}"'))
        add(a, P["comment"], entity(f'"{kind.lower()} {i} comment"'))
        add(a, P["homepage"], entity(f'"http://{kind.lower()}{i}.example"'))
        add(a, P["country"],
            countries[int(rng.choice(len(countries), p=country_w))])
        return a

    producers = [agent("Producer", i) for i in range(n_producers)]
    vendors = [agent("Vendor", i) for i in range(n_vendors)]
    reviewers = []
    for i in range(n_reviewers):
        person = entity(f"b:Reviewer{i}")
        add(person, typ, shared("b:Person"))
        add(person, P["name"], entity(f'"reviewer {i}"'))
        add(person, P["mbox_sha1sum"], entity(f'"{i:040x}"'))
        add(person, P["country"],
            countries[int(rng.choice(len(countries), p=country_w))])
        reviewers.append(person)

    dates = [shared(f'"2008-{m:02d}-{d:02d}"')
             for m in range(1, 13) for d in range(1, 29)]
    lo_num, hi_num = r["property_numeric"]
    lo_f, hi_f = r["features_of_product"]
    lo_p, hi_p = r["price"]
    lo_dd, hi_dd = r["delivery_days"]
    opt_num = r["optional_property_share"]
    rating_share = r["rating_share"]
    home_share = r["reviewer_homepage_share"]
    n_off = int(r["offers_per_product"])
    n_rev = int(r["reviews_per_product"])
    n_feats = sizes.integers(lo_f, hi_f + 1, size=n_prod).tolist()
    present = (sizes.random((n_prod, 6)) < opt_num).tolist()
    rated = (sizes.random((n_prod, n_rev, 4)) < rating_share).tolist()
    homes = (sizes.random((n_prod, n_rev)) < home_share).tolist()
    for p in range(n_prod):
        prod = entity(f"b:Product{p}")
        b.population("product", prod)
        add(prod, typ, shared("b:Product"))
        add(prod, typ, types[int(rng.integers(n_types))])
        add(prod, P["label"], entity(f'"product {p}"'))
        add(prod, P["comment"], entity(f'"product {p} comment"'))
        add(prod, P["producer"], producers[int(rng.integers(n_producers))])
        add(prod, P["publisher"], producers[int(rng.integers(n_producers))])
        add(prod, P["publishDate"], dates[int(rng.integers(len(dates)))])
        for f in rng.choice(n_feat, size=min(n_feats[p], n_feat),
                            replace=False).tolist():
            add(prod, P["productFeature"], features[f])
        nums = rng.integers(lo_num, hi_num + 1, size=6).tolist()
        for i in range(6):
            if i < 3 or present[p][i]:
                add(prod, P[f"propertyNumeric{i + 1}"], shared(f'"{nums[i]}"'))
                add(prod, P[f"propertyTextual{i + 1}"],
                    entity(f'"product {p} text {i + 1}"'))
        prices = rng.uniform(lo_p, hi_p, size=n_off).tolist()
        vend = rng.integers(n_vendors, size=n_off).tolist()
        days = rng.integers(lo_dd, hi_dd + 1, size=n_off).tolist()
        d_from = rng.integers(len(dates), size=n_off).tolist()
        for j in range(n_off):
            off = entity(f"b:Offer{p}.{j}")
            add(off, typ, shared("b:Offer"))
            add(off, P["product"], prod)
            add(off, P["vendor"], vendors[vend[j]])
            add(off, P["price"], shared(f'"{prices[j]:.2f}"'))
            add(off, P["validFrom"], dates[d_from[j]])
            add(off, P["validTo"], dates[(d_from[j] + 30) % len(dates)])
            add(off, P["deliveryDays"], shared(f'"{days[j]}"'))
            add(off, P["offerWebpage"],
                entity(f'"http://vendor.example/offer{p}.{j}"'))
            add(off, P["publisher"], vendors[vend[j]])
            add(off, P["publishDate"], dates[d_from[j]])
        who = rng.integers(n_reviewers, size=n_rev).tolist()
        ratings = rng.integers(1, 11, size=(n_rev, 4)).tolist()
        when = rng.integers(len(dates), size=n_rev).tolist()
        for j in range(n_rev):
            rev = entity(f"b:Review{p}.{j}")
            add(rev, typ, shared("b:Review"))
            add(rev, P["reviewFor"], prod)
            add(rev, P["reviewer"], reviewers[who[j]])
            add(rev, P["reviewDate"], dates[when[j]])
            add(rev, P["title"], entity(f'"review {p}.{j}"'))
            add(rev, P["text"], entity(f'"review {p}.{j} text"'))
            for i in range(4):
                if rated[p][j][i]:
                    add(rev, P[f"rating{i + 1}"],
                        shared(f'"{ratings[j][i]}"'))
            if homes[p][j]:
                add(rev, P["reviewerHomepage"],
                    entity(f'"http://reviewer.example/{p}/{j}"'))
            add(rev, P["publisher"], reviewers[who[j]])
            add(rev, P["publishDate"], dates[when[j]])
    return b.build()
