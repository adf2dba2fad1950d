"""prepare_host_share (%): the share of the traced window the host
spent in ``repro.scan.prepare``, before each ``run_rounds`` scan: the
blockers, the cohort pool, the cold carry or warm resume, the scan's
operands and the compiled-program lookup."""
from bench.lib import layers


def read(run):
    return layers.host_share(run, "repro.scan.prepare")
