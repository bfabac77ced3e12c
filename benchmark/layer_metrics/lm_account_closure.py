"""The step's account closes: the sum of ALL buckets of
``benchmark/step_account.py`` (owned, ``unowned`` and ``unscoped``) over the
device-busy time of the traced window (``lm_step_device_ms`` x traced steps),
in percent.

100 where the ops' self-times tile the window's busy time: the account and
the accepted step reader then see the same device time.  Under 100: ops that
straddle the window's edge (counted busy, in no scope sum) or overlap on the
device; over 100: self-times counted twice.  Higher is not better past 100:
the reading to hold is 99.5 to 100.5."""

from benchmark import step_account

LAYER = "step, model"
UNIT = "%"
MOVES = "train_tok_rate"


def read(run: dict):
    return step_account.closure(run)
