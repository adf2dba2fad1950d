"""finalize_host_share (%): the share of the traced window the host
spent in ``repro.scan.finalize``, the hand-off after each ``run_rounds``
scan, less its ``repro.finalize.wait`` (the host waiting for the scan):
the copies to the host and the host-side rebuild."""
from bench.lib import layers


def read(run):
    return layers.host_share(run, "repro.scan.finalize",
                             minus=("repro.finalize.wait",))
