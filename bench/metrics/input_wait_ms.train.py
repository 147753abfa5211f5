"""Time a training step waits for data: device idle time per step inside
the engine's ``tl_input_wait`` host spans (the step loop blocked on its
next batch), all of it, including the part under the prefetch thread's
``tl_put_batch``, ms."""
from bench.lib.scopes import install

install()


def read(run):
    idle = run["trace"].get("idle_within_span") or {}
    steps = run["host"].get("steps")
    if run["traffic"]["driver"] != "train" or not steps \
            or "tl_input_wait" not in idle:
        return None
    return 1e3 * idle["tl_input_wait"] / steps
