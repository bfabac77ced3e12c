"""Model FLOP/s utilization per image (see ``mfu``), in the four-chip cell."""

LAYER = "step, model"
UNIT = "%"
MOVES = "train_img_rate_dp4"


def read(run: dict):
    peaks, facts = run.get("peaks"), run["facts"]
    if not peaks:
        return None
    return (100.0 * facts["flops_per_sample"] * facts["rate_per_chip"]
            / peaks["bf16_flops_per_s"])
