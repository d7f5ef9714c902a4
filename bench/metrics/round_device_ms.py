"""Round program: device busy time per round (ms).

The union of the device's op intervals in the traced window (the
``while`` of the round's scan and other containers left out), averaged
over the chips used, per round completed in the window.
"""


def read(ctx):
    if ctx.rounds < 1 or ctx.busy_s <= 0:
        return None
    return 1e3 * ctx.busy_s / ctx.rounds
