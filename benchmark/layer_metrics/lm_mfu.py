"""Model FLOP/s utilization per token (see ``mfu``), in the cells that count
tokens."""

LAYER = "step, model"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    peaks, facts = run.get("peaks"), run["facts"]
    if not peaks:
        return None
    return (100.0 * facts["flops_per_sample"] * facts["rate_per_chip"]
            / peaks["bf16_flops_per_s"])
