"""Set-up seconds: process start to the first timed step (imports, model
init and packing from the seed, engine warm-up and its compiles)."""


def read(run, trace):
    return run.setup["setup_s"]
