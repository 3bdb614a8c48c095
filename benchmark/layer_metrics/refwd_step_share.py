"""What ``jax.checkpoint``'s second forward costs: device self time of
the step program's operations whose ``op_name`` holds
``rematted_computation``, over the program's device time in the profiled
rounds.

``step_scoped_share.py`` reads the step (its account, shared) and says
how the pass is told; its log line ``step_passes`` splits this figure by
scope, which prices the next name a checkpointed layer could keep
(``REMAT_SAVED``): a name is worth what the second forward spends on it.
``local_mfu`` counts none of this time as useful."""

from benchmark.layer_metrics.step_scoped_share import step_account

NAME, UNIT = "refwd_step_share", "%"
LAYER = "local step"
MOVES = "fed_items_per_s"
SOURCE = "device_trace"
CELLS = ["*"]


def read(ctx):
    found = step_account(ctx)
    if not found or not found["program"]:
        return None
    again = sum(row["refwd"] for row in found["passes"].values())
    return 100.0 * again / found["program"]
