"""Process start to the first measured request: imports, weights made on the
device, engine construction, the mix's own set-up traffic, and every compile
(from the persistent cache after a checkout's first run)."""


def read(run):
    return run["setup_s"]
