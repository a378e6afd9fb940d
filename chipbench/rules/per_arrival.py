"""FedAsync's commit rule, stated plainly.

Every arrival instant commits: the round's first trains and aggregates
what has landed, later ones drain the carried models as they land.  Each
model enters as a moving average with ``alpha = 0.5 / (1 + staleness)``,
in commit order.
"""
import numpy as np

GROUPING = False
# each model's weight follows its place in the commit's order, not its
# data size: a block is one model
BLOCKS = "model"


def round_commits(arrivals, t_start, cfg):
    """One commit per distinct arrival instant; the first takes the
    arrivals at that instant and carries the rest."""
    if not arrivals:
        raise ValueError("a round with no arrival: the reference does not "
                         "follow the runtime there")
    times = sorted({a[0] for a in arrivals})
    t1 = times[0]
    out = [(t1, [a for a in arrivals if a[0] <= t1],
            [a for a in arrivals if a[0] > t1])]
    return out + [(t, [], []) for t in times[1:]]


def weights(models, beta, groups):
    alphas = [0.5 / (1.0 + max(beta - ep, 0)) for (_s, _n, ep) in models]
    ws = np.zeros(len(models))
    keep = 1.0
    for i in reversed(range(len(models))):
        ws[i] = alphas[i] * keep
        keep *= 1.0 - alphas[i]
    return ws, keep, 1.0, 0
