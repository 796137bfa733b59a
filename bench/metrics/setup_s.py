"""setup_s: seconds from the start of the process to the first timed job:
imports, device start, fields made from the seed, program build and the
warm-up job (compilation, or reads of the persistent compile cache)."""


def read(run):
    return run.setup_s
