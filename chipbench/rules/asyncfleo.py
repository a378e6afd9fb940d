"""AsyncFLEO's commit rule (paper Alg. 2, eqs. 13-14), stated plainly.

A round commits once: the first arrival at the sink opens a collection
window of ``agg_timeout_s``; what lands inside it is aggregated, the rest
is carried to later commits.  Fewer than ``min_models`` arrivals in the
window: the first ``min_models`` are taken and the commit moves to the last
of them.  Orbits are grouped by the distance of their partial model to
``w0``; a group with a fresh model drops its stale ones, a group with only
stale models joins, discounted by eq. 13.
"""
import numpy as np

GROUPING = True
# within one commit, the models of one orbit trained from one epoch weigh
# in proportion to their data sizes (eq. 14; Alg. 2 selects whole orbits)
BLOCKS = "orbit"
MIN_GAMMA = 0.1


def round_commits(arrivals, t_start, cfg):
    """The round's commits as (t_agg, used, late): one, at the end of the
    window that its first arrival opens."""
    if not arrivals:
        raise ValueError("a round with no arrival: the reference does not "
                         "follow the runtime there")
    t_agg = min(arrivals[0][0] + cfg["agg_timeout_s"], cfg["horizon_s"])
    used = [a for a in arrivals if a[0] <= t_agg]
    if len(used) < cfg["min_models"]:
        used = arrivals[:cfg["min_models"]]
        t_agg = used[-1][0]
    return [(t_agg, used, arrivals[len(used):])]


def weights(models, beta, groups):
    """Per-model weights, the old model's weight, gamma and the number of
    stale-only groups.  ``models``: (sat, size, epoch) in commit order;
    ``groups``: lists of indices into ``models``."""
    fresh = [ep >= beta for (_s, _n, ep) in models]
    selected, stale_groups = [], 0
    for idxs in groups:
        f = [i for i in idxs if fresh[i]]
        if f:
            selected += f
        else:
            selected += idxs
            stale_groups += 1
    ws = np.zeros(len(models))
    if not selected:
        return ws, 1.0, 0.0, 0
    sizes = np.array([models[i][1] for i in selected], np.float64)
    epochs = np.array([models[i][2] for i in selected], np.float64)
    is_fresh = np.array([fresh[i] for i in selected])
    if is_fresh.all():
        gamma, raw = 1.0, sizes
    else:
        share = np.clip(epochs, 0, None) / max(beta, 1)
        gamma = (float(np.clip(np.sum(sizes / sizes.sum() * share), 0, 1))
                 if beta > 0 else 1.0)
        gamma = max(gamma, MIN_GAMMA)
        raw = sizes * np.where(is_fresh, 1.0, share)
        if raw.sum() <= 0:
            raw = sizes
    ws[selected] = gamma * raw / raw.sum()
    return ws, 1.0 - gamma, gamma, stale_groups
