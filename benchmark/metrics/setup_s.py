"""setup_s: process start to window start (imports, warm-up, fixtures,
round objects, committee upload, one call)."""


def read(run):
    return run.setup_s
