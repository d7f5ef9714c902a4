"""Host control plane: plan + batch build per steady round (ms).

Mean over the rounds completed in the traced window of the executor's
``host/plan`` and ``host/build`` spans (``repro.obs.trace``, host clock).
"""


def read(ctx):
    rounds = set(ctx.round_ids)
    per: dict = {}
    for lane, t0, t1, args in ctx.spans:
        r = args.get("round")
        if lane in ("host/plan", "host/build") and r in rounds:
            per[r] = per.get(r, 0.0) + (t1 - t0)
    if not per:
        return None
    return 1e3 * sum(per.values()) / len(per)
