"""Process start to the first due request of the window: data
generation, the program's load path, device upload and warm-up."""


def read(run):
    return run.setup_s
