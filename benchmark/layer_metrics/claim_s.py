"""Seconds from ``tos.run`` to the node's device block in ``cluster_info()``
(driver clock): process spawn, imports, registration, the chip claim."""

LAYER = "process start"
UNIT = "s"
MOVES = "setup_s"


def read(run: dict):
    return run["facts"].get("claim_s")
