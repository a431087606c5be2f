"""Process start to the first timed call: data generation, sessions or the
server, plan resolution, compilation (or cache loads) and the warm-up."""


def read(run):
    return run.setup_s
