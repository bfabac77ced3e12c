"""Milliseconds per step in the Pallas flash-attention forward kernel: the
``tpu_custom_call`` events of the trace, all layers, mean over devices."""

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tok_rate"


def read(run: dict):
    steps, trace = run["facts"].get("traced_steps"), run.get("trace")
    if not steps or not trace or not trace.get("pallas_calls"):
        return None
    return 1e3 * trace["pallas_s"] / steps
