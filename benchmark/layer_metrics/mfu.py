"""Model FLOP/s utilization: the FLOPs training needs per image (from the
configuration's shapes, 2 per multiply-add, no recompute) times images per
second per chip, over the chip's bf16 peak (``peaks.json``)."""

LAYER = "step, model"
UNIT = "%"
MOVES = "train_img_rate"


def read(run: dict):
    peaks, facts = run.get("peaks"), run["facts"]
    if not peaks:
        return None
    return (100.0 * facts["flops_per_sample"] * facts["rate_per_chip"]
            / peaks["bf16_flops_per_s"])
