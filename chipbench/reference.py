"""Plain reference of a lap's first commits, from the seed alone.

It imports nothing of the program under test and takes nothing the program
made.  Everything it follows is stated by the configuration file (the
deployment: constellation, parameter-server coordinates, link, data,
training) and the mix file (the strategy's ``spec``: which parameter
server, which commit rule).  In straightforward numpy and ``jax.numpy``:

* the data: seeded class-conditional images and the configuration's
  partition (the same generator rules the launcher uses);
* the initial model: the CNN's fan-in truncated-normal initialisation;
* each round's schedule: Walker-delta orbits, an Earth-fixed parameter
  server, elevation gating on the ``dt_s`` grid, the link delay, the
  downlink with intra-orbit ISL relay and the uplink (Alg. 1);
* local training of every participant of a round from the reference's own
  global model: ``local_iters`` SGD steps on minibatches drawn from the
  participant's key (paper eq. 3);
* the commits: the rule ``chipbench/rules/<agg_mode>.py`` says when a round
  commits and with which weights; carried models re-enter later commits
  with the epoch they trained from, a satellite counts once (its newest
  model), and orbits are grouped by distance (rules that group).

The schedule (when each commit falls, which models it takes and from
which epoch) is followed over the whole lap; it needs no tensor.  The models
are followed for ``MIN_COMMITS`` commits and on to the first commit that
holds a stale model, at most ``MAX_COMMITS``.  A single parameter server and
one round in flight are what it states; a mix that asks for more is
refused.

``precision`` selects how the tensor work is computed: ``"highest"`` is
the reference; the controls are the same code one precision step below
what the configuration states (``"bfloat16"`` training, or ``"high"``
server contractions).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
import os
from typing import Dict, List, Tuple

import numpy as np

R_EARTH = 6371.0e3
GM = 3.986004418e14
OMEGA_EARTH = 7.2921159e-5
C_LIGHT = 299_792_458.0
MIN_COMMITS = 3
MAX_COMMITS = 16
RULES_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "rules")


def load_rule(agg_mode: str):
    """The commit rule ``rules/<agg_mode>.py``."""
    path = os.path.join(RULES_DIR, agg_mode + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_rule_" + agg_mode, path)
    if spec is None or not os.path.isfile(path):
        raise ValueError(f"no reference commit rule {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---- data -------------------------------------------------------------------

def _prototypes(rng, num_classes, size, channels, smooth=3):
    protos = rng.standard_normal((num_classes, size, size, channels))
    for _ in range(smooth):
        protos = (protos
                  + np.roll(protos, 1, 1) + np.roll(protos, -1, 1)
                  + np.roll(protos, 1, 2) + np.roll(protos, -1, 2)) / 5.0
    protos -= protos.mean(axis=(1, 2, 3), keepdims=True)
    protos /= protos.std(axis=(1, 2, 3), keepdims=True) + 1e-9
    return protos


def images(seed: int, n: int, size: int, channels: int, separation: float,
           num_classes: int = 10):
    """Seeded class-conditional images in [0, 1] and their labels."""
    rng = np.random.default_rng(seed)
    protos = _prototypes(np.random.default_rng(1234), num_classes, size,
                         channels)
    labels = rng.integers(0, num_classes, size=n)
    noise = rng.standard_normal((n, size, size, channels))
    noise = (noise + np.roll(noise, 1, 1) + np.roll(noise, 1, 2)) / 3.0
    x = separation * protos[labels] + noise
    x = (x - x.min()) / (x.max() - x.min() + 1e-9)
    return x.astype(np.float32), labels.astype(np.int32)


def shards_of(labels, cfg: Dict, seed: int) -> List[np.ndarray]:
    """The paper's partition of the training set over satellites: those of
    the first two orbits hold classes 0-3, the others classes 4-9, each
    class pool dealt out in order."""
    if cfg["partition"] != "paper_noniid":
        raise ValueError(f"partition {cfg['partition']!r}")
    c = cfg["constellation"]
    N = c["sats_per_orbit"]
    orbit_of_sat = np.arange(c["num_orbits"] * N) // N
    rng = np.random.default_rng(seed)
    low = np.flatnonzero(labels < 4)
    high = np.flatnonzero(labels >= 4)
    rng.shuffle(low)
    rng.shuffle(high)
    out = [None] * len(orbit_of_sat)
    for sats, pool in ((np.flatnonzero(orbit_of_sat < 2), low),
                       (np.flatnonzero(orbit_of_sat >= 2), high)):
        for s, chunk in zip(sats, np.array_split(pool, len(sats))):
            out[int(s)] = np.sort(chunk)
    return out


def shard_rows(seed: int, cfg: Dict) -> int:
    """Samples per satellite once shards are cut to the smallest."""
    labels = np.random.default_rng(seed).integers(
        0, cfg["num_classes"], size=cfg["train_samples"])
    return min(len(s) for s in shards_of(labels, cfg, seed))


# ---- model ------------------------------------------------------------------

def init_params(seed: int, cfg: Dict):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    c1, c2 = cfg["conv_channels"]
    ch, hid, ncls = cfg["channels"], cfg["hidden"], cfg["num_classes"]
    flat = (cfg["image_size"] // 4) ** 2 * c2

    def tn(k, shape, fan_in):
        std = 1.0 / math.sqrt(fan_in)
        return jax.random.truncated_normal(k, -2.0, 2.0, shape) * std

    return {"conv1": tn(ks[0], (3, 3, ch, c1), 9 * ch),
            "bc1": jnp.zeros((c1,)),
            "conv2": tn(ks[1], (3, 3, c1, c2), 9 * c1),
            "bc2": jnp.zeros((c2,)),
            "w1": tn(ks[2], (flat, hid), flat),
            "b1": jnp.zeros((hid,)),
            "w2": tn(ks[3], (hid, ncls), hid),
            "b2": jnp.zeros((ncls,))}


def _precision(precision: str):
    import jax
    return {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH,
            "default": jax.lax.Precision.DEFAULT,
            "bfloat16": jax.lax.Precision.DEFAULT}[precision]


def logits(params, x, precision: str = "highest"):
    """Conv 3x3 SAME + ReLU + 2x2 max-pool, twice, then two dense layers."""
    import jax
    import jax.numpy as jnp
    p = _precision(precision)

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=p)
        return jax.nn.relu(y + b)

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = pool(conv(x, params["conv1"], params["bc1"]))
    x = pool(conv(x, params["conv2"], params["bc2"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["w1"], precision=p) + params["b1"])
    return jnp.dot(x, params["w2"], precision=p) + params["b2"]


def xent(params, x, y, precision: str = "highest"):
    import jax
    import jax.numpy as jnp
    z = logits(params, x, precision).astype(jnp.float32)
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return (jax.nn.logsumexp(z, axis=-1) - gold).mean()


def train_participants(w, imgs, labs, ids, epoch_seed: int, cfg: Dict,
                       local_iters: int, precision: str = "highest"):
    """Every participant's ``local_iters`` SGD steps from ``w``: rows of
    trained parameters (a pytree with a leading participant axis) and each
    participant's mean step loss.  Participant ``i`` trains on
    ``imgs[i], labs[i]`` with key ``PRNGKey(epoch_seed * 9973 + ids[i])``
    (uint32 arithmetic), drawing ``batch_size`` samples per step."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    lr, bs = cfg["lr"], cfg["batch_size"]

    def one(w, x, y, sid, epoch_seed):
        key = jax.random.PRNGKey(epoch_seed * jnp.uint32(9973)
                                 + sid.astype(jnp.uint32))
        n = x.shape[0]
        params = jax.tree.map(lambda a: a.astype(dtype), w)
        x = x.astype(dtype)

        def step(params, k):
            idx = jax.random.randint(k, (bs,), 0, n)
            loss, g = jax.value_and_grad(xent)(params, x[idx], y[idx],
                                               precision)
            return jax.tree.map(lambda a, b: a - lr * b, params, g), loss

        params, losses = jax.lax.scan(step, params,
                                      jax.random.split(key, local_iters))
        return (jax.tree.map(lambda a: a.astype(jnp.float32), params),
                losses.astype(jnp.float32).mean())

    # w and the seed are arguments, not constants, so one compiled program
    # (and its persistent-cache entry) serves every round and seed
    fn = jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, None)))
    return fn(w, jnp.asarray(imgs), jnp.asarray(labs),
              jnp.asarray(ids, jnp.int32), jnp.uint32(epoch_seed))


def flatten(tree) -> np.ndarray:
    """Leaves in sorted-key order, each raveled, as one float64 vector."""
    return np.concatenate([np.ravel(np.asarray(tree[k], np.float64))
                           for k in sorted(tree)])


def leaf_slices(tree) -> Dict[str, slice]:
    out, at = {}, 0
    for k in sorted(tree):
        n = int(np.prod(np.shape(tree[k])))
        out[k] = slice(at, at + n)
        at += n
    return out


def unflatten(flat, like) -> Dict:
    """A float32 pytree shaped like ``like`` from a flat vector."""
    out = {}
    for k, sl in leaf_slices(like).items():
        out[k] = np.asarray(flat[sl], np.float32).reshape(np.shape(like[k]))
    return out


# ---- schedule ---------------------------------------------------------------

@dataclasses.dataclass
class Geometry:
    num_orbits: int
    sats_per_orbit: int
    altitude_m: float
    inclination_deg: float
    phasing: int
    ps: Dict                 # lat_deg, lon_deg, altitude_m, min_elevation_deg
    horizon_s: float
    dt_s: float

    def __post_init__(self):
        self.S = self.num_orbits * self.sats_per_orbit
        self.r = R_EARTH + self.altitude_m
        v = float(np.sqrt(GM / self.r))
        self.n = 2 * np.pi / float(2 * np.pi * self.r / v)
        self.times = np.arange(0.0, self.horizon_s + self.dt_s, self.dt_s)
        alt = self.ps["altitude_m"]
        dip = (float(np.rad2deg(np.arccos(R_EARTH / (R_EARTH + alt))))
               if alt > 0 else 0.0)
        self.min_el = self.ps["min_elevation_deg"] - dip
        pos = self.sat_pos(np.arange(self.S)[None, :], self.times[:, None])
        gnd = self.ps_pos(self.times)[:, None, :]
        d = pos - gnd
        sin_el = (np.sum(d * gnd, -1)
                  / np.maximum(np.linalg.norm(d, axis=-1)
                               * np.linalg.norm(gnd, axis=-1), 1e-9))
        el = np.rad2deg(np.arcsin(np.clip(sin_el, -1.0, 1.0)))
        self.grid = el >= self.min_el                      # (T, S)

    def sat_pos(self, sat, t):
        sat, t = np.broadcast_arrays(np.asarray(sat), np.asarray(t, float))
        O, N = self.num_orbits, self.sats_per_orbit
        o, s = sat // N, sat % N
        raan = 2 * np.pi * o / O
        u = (2 * np.pi * s / N + self.phasing * 2 * np.pi * o / (O * N)
             + self.n * t)
        inc = np.deg2rad(self.inclination_deg)
        xp, yp = self.r * np.cos(u), self.r * np.sin(u)
        x1, y1, z1 = xp, yp * np.cos(inc), yp * np.sin(inc)
        return np.stack([x1 * np.cos(raan) - y1 * np.sin(raan),
                         x1 * np.sin(raan) + y1 * np.cos(raan), z1], -1)

    def ps_pos(self, t):
        t = np.asarray(t, float)
        lat = np.deg2rad(self.ps["lat_deg"])
        lon = np.deg2rad(self.ps["lon_deg"])
        r = R_EARTH + self.ps["altitude_m"]
        th = lon + OMEGA_EARTH * t
        return np.stack([r * np.cos(lat) * np.cos(th),
                         r * np.cos(lat) * np.sin(th),
                         np.full_like(th, r * np.sin(lat))], -1)

    def row(self, t):
        return np.clip(np.round(np.asarray(t) / self.dt_s).astype(np.int64),
                       0, len(self.times) - 1)

    def ring(self, a, b):
        d = np.abs(np.asarray(a) % self.sats_per_orbit
                   - np.asarray(b) % self.sats_per_orbit)
        return np.minimum(d, self.sats_per_orbit - d)

    def next_orbit_visible(self, orbit: int, t: float):
        N = self.sats_per_orbit
        sats = np.arange(orbit * N, (orbit + 1) * N)
        sub = self.grid[int(self.row(t)):, sats]
        rows = np.flatnonzero(sub.any(axis=1))
        if not len(rows):
            return None, None
        r = rows[0]
        return (float(self.times[int(self.row(t)) + r]),
                int(sats[np.flatnonzero(sub[r])[0]]))


class Links:
    """Delays on the configuration's link: transmission, propagation and
    the fixed processing delay, to the parameter server and along the
    intra-orbit ring."""

    def __init__(self, geo: Geometry, bits: float, cfg: Dict):
        self.geo = geo
        self.tx = bits / cfg["link_rate_bps"]
        self.proc = cfg["proc_delay_s"]
        chord = float(2 * geo.r * np.sin(np.pi / geo.sats_per_orbit))
        self.hop = self.tx + chord / C_LIGHT + self.proc

    def to_ps(self, sat, t):
        g = self.geo
        d = np.linalg.norm(g.sat_pos(sat, t) - g.ps_pos(t), axis=-1)
        return self.tx + d / C_LIGHT + self.proc


def downlink(geo: Geometry, links: Links, t0: float) -> np.ndarray:
    """Receive time of the global model sent at ``t0``: the parameter
    server sends to the satellites it sees, the ISL rings relay on; an
    orbit that sees nothing waits for its pass."""
    S, N = geo.S, geo.sats_per_orbit
    recv = np.full(S, np.inf)
    vis = np.flatnonzero(geo.grid[int(geo.row(t0))])
    recv[vis] = t0 + links.to_ps(vis, t0)
    ringd = geo.ring(np.arange(N)[:, None], np.arange(N)[None, :])
    on = recv.reshape(-1, N)
    on = np.minimum(on, (on[:, :, None] + ringd[None] * links.hop).min(1))
    for o in np.flatnonzero(~np.isfinite(on).any(axis=1)):
        t_vis, seed = geo.next_orbit_visible(int(o), t0)
        if t_vis is None:
            continue
        t_seed = max(t_vis, t0) + links.to_ps(seed, t_vis)
        on[o] = np.minimum(on[o], t_seed + ringd[seed % N] * links.hop)
    return on.reshape(S)


def uplink(geo: Geometry, links: Links, sat: int, td: float) -> float:
    """Arrival at the parameter server of a model done at ``td``: straight
    up when the server is in view, else along the ring to the nearest
    orbit-mate in view, else at the orbit's next pass."""
    N = geo.sats_per_orbit
    r = int(geo.row(td))
    if geo.grid[r, sat]:
        return float(td + links.to_ps(sat, td))
    o = sat // N
    mates = np.arange(o * N, (o + 1) * N)
    seen = geo.grid[r, mates]
    if seen.any():
        dist = np.where(seen, geo.ring(sat, mates), 10 ** 9)
        j = int(np.argmin(dist))
        ta = td + dist[j] * links.hop
        return float(ta + links.to_ps(mates[j], ta))
    t_vis, star = geo.next_orbit_visible(o, td)
    if t_vis is None:
        return float("inf")
    ready = max(td + int(geo.ring(sat, star)) * links.hop, t_vis)
    return float(ready + links.to_ps(star, ready))


# ---- grouping ---------------------------------------------------------------

def group_by_gaps(dist: Dict[int, float], num_groups: int) -> List[List[int]]:
    """Orbits sorted by distance, cut at the ``num_groups - 1`` widest
    gaps."""
    orbits = sorted(dist, key=lambda o: dist[o])
    if len(orbits) <= num_groups:
        return [[o] for o in orbits]
    gaps = np.diff([dist[o] for o in orbits])
    cuts = sorted(np.argsort(gaps)[::-1][:num_groups - 1])
    out, start = [], 0
    for c in cuts:
        out.append(orbits[start:c + 1])
        start = c + 1
    return out + [orbits[start:]]


class Grouping:
    """Orbits enter one at a time: while there are fewer than
    ``num_groups`` groups, every distance seen is regrouped by gaps; after
    that a new orbit joins the group of nearest mean distance."""

    def __init__(self, num_groups: int):
        self.num_groups = num_groups
        self.dist: Dict[int, float] = {}
        self.groups: List[List[int]] = []

    def add(self, orbit: int, d: float) -> None:
        self.dist[orbit] = float(d)
        if len(self.groups) < self.num_groups:
            self.groups = group_by_gaps(self.dist, self.num_groups)
            return
        means = [np.mean([self.dist[o] for o in g]) for g in self.groups]
        self.groups[int(np.argmin([abs(d - m) for m in means]))].append(
            orbit)

    def known(self, orbit: int) -> bool:
        return orbit in self.dist


# ---- the lap ----------------------------------------------------------------

@dataclasses.dataclass
class Slot:
    """One commit of the lap's schedule: no tensors."""
    t_agg: float
    train: Tuple[int, int]           # (round, beta) it trains, or (-1, -1)
    used: List[tuple]                # (t_arr, sat, row) of its own round
    models: List[tuple]              # (t_arr, sat, epoch, (round, row))


@dataclasses.dataclass
class Commit:
    t_agg: float
    models: List[Tuple[int, int]]    # (sat, epoch trained from), in order
    weights: Dict[int, float]        # sat -> aggregation weight
    base_weight: float
    gamma: float
    stale_groups: int
    groups: List[List[int]]          # indices into ``models``
    w: np.ndarray                    # the global model after it, float64


@dataclasses.dataclass
class Lap:
    schedule: List[Slot]             # every commit of the lap
    participants: List[int]          # round 0's
    rows: Dict                       # round 0's trained rows (P, ...)
    used: List[int]                  # sat ids of the first commit, in order
    sizes: Dict[int, int]            # data size per satellite
    w0: Dict                         # host float32 leaves
    dists: Dict[int, float]          # first commit: orbit -> distance
    commits: List[Commit]            # the commits followed with models


def contract(ws, flat_rows, base_w: float, w_flat, precision: str):
    """eq. 14: ``base_w * w + ws @ rows``; float64 on the host for the
    reference, the device at ``precision`` for a control."""
    if precision == "highest":
        return (base_w * np.asarray(w_flat, np.float64)
                + np.asarray(ws, np.float64) @ np.asarray(flat_rows,
                                                          np.float64))
    import jax.numpy as jnp
    out = (jnp.float32(base_w) * jnp.asarray(w_flat, jnp.float32)
           + jnp.dot(jnp.asarray(ws, jnp.float32),
                     jnp.asarray(flat_rows, jnp.float32),
                     precision=_precision(precision)))
    return np.asarray(out, np.float64)


def orbit_distances(sats, flat_rows, sizes, w0_flat, per_orbit: int,
                    precision: str = "highest") -> Dict[int, float]:
    """Grouping distances: per orbit, the size-weighted mean of its models'
    rows, against ``w0`` (Euclidean)."""
    orbits = list(dict.fromkeys(s // per_orbit for s in sats))
    wmat = np.zeros((len(orbits), len(sats)))
    for j, s in enumerate(sats):
        wmat[orbits.index(s // per_orbit), j] = sizes[s]
    wmat /= wmat.sum(axis=1, keepdims=True)
    if precision == "highest":
        pm = wmat @ np.asarray(flat_rows, np.float64)
    else:
        import jax.numpy as jnp
        pm = np.asarray(jnp.dot(jnp.asarray(wmat, jnp.float32),
                                jnp.asarray(flat_rows, jnp.float32),
                                precision=_precision(precision)), np.float64)
    d = np.linalg.norm(pm - np.asarray(w0_flat, np.float64)[None, :], axis=1)
    return {o: float(d[i]) for i, o in enumerate(orbits)}


def _newest_per_sat(models):
    """A satellite counts once: its model of the latest arrival (the first
    of equal arrivals)."""
    best: Dict[int, int] = {}
    for i, mm in enumerate(models):
        s = mm[1]
        if s not in best or models[best[s]][0] < mm[0]:
            best[s] = i
    return [models[i] for i in sorted(best.values())]


def _geometry(cfg: Dict, spec: Dict) -> Geometry:
    if not spec["use_isl"]:
        raise ValueError("the reference states the ISL relay only")
    c = cfg["constellation"]
    return Geometry(c["num_orbits"], c["sats_per_orbit"], c["altitude_m"],
                    c["inclination_deg"], c["phasing"],
                    cfg["ps"][spec["ps_scenario"]], cfg["horizon_s"],
                    cfg["dt_s"])


def schedule(cfg: Dict, spec: Dict, bits: float) -> Tuple[List[Slot],
                                                           List[List[int]]]:
    """Every commit of a lap, and each round's participants: one round in
    flight, opened at the previous round's last commit; models carried
    past a commit re-enter the first commit after they land."""
    rule = load_rule(spec["agg_mode"])
    geo = _geometry(cfg, spec)
    links = Links(geo, bits, cfg)
    t, beta = 0.0, 0
    pending: List[tuple] = []
    slots: List[Slot] = []
    rounds: List[List[int]] = []
    while t < cfg["horizon_s"]:
        recv = downlink(geo, links, t)
        participants = [s for s in range(geo.S) if np.isfinite(recv[s])]
        t_arr = [uplink(geo, links, s, recv[s] + cfg["train_time_s"])
                 for s in participants]
        arrivals = sorted(((ta, s, k) for k, (s, ta)
                           in enumerate(zip(participants, t_arr))
                           if np.isfinite(ta)), key=lambda a: a[0])
        if not arrivals and not pending:
            break
        r, round_beta = len(rounds), beta
        rounds.append(participants)
        for i, (t_agg, used, late) in enumerate(
                rule.round_commits(arrivals, t, cfg)):
            models = [(ta, s, round_beta, (r, k)) for (ta, s, k) in used]
            models += [p for p in pending if p[0] <= t_agg]
            pending = ([p for p in pending if p[0] > t_agg]
                       + [(ta, s, round_beta, (r, k)) for (ta, s, k) in late])
            slots.append(Slot(t_agg, (r, beta) if i == 0 else (-1, -1),
                              used, _newest_per_sat(models)))
            beta += 1
            t = t_agg
    return slots, rounds


def lap(cfg: Dict, spec: Dict, seed: int, *, local_iters: int,
        precision: str = "highest", server_precision: str = "highest"
        ) -> Lap:
    """The reference's lap for ``seed`` (a run seed) under the mix's
    ``spec`` (``ps_scenario``, ``agg_mode``, ``use_isl``, ``num_groups``):
    its whole schedule, and its models, weights and global model for the
    first commits, up to the first that holds a stale model."""
    import jax
    rule = load_rule(spec["agg_mode"])
    N = cfg["constellation"]["sats_per_orbit"]
    x, y = images(seed, cfg["train_samples"], cfg["image_size"],
                  cfg["channels"], cfg["separation"])
    shards = shards_of(y, cfg, seed)
    sizes = {s: len(sh) for s, sh in enumerate(shards)}
    m = min(sizes.values())
    w0 = {k: np.asarray(v) for k, v in init_params(seed, cfg).items()}
    w0_flat = flatten(w0)
    slots, rounds = schedule(cfg, spec, 32.0 * w0_flat.size)
    if len(slots) < MIN_COMMITS:
        raise ValueError(f"the lap has {len(slots)} commits, fewer than "
                         f"the {MIN_COMMITS} the check follows")
    stale = [k for k, sl in enumerate(slots)
             if any(mm[2] < k for mm in sl.models)]
    n = min(max(MIN_COMMITS, stale[0] + 1 if stale else 0), MAX_COMMITS,
            len(slots))
    grouping = Grouping(spec["num_groups"]) if rule.GROUPING else None

    w = w0_flat
    rows: Dict[int, np.ndarray] = {}
    first: Dict = {}
    commits: List[Commit] = []
    for beta, sl in enumerate(slots[:n]):
        r, _b = sl.train
        if r >= 0:
            ps = rounds[r]
            sel = np.stack([shards[s][:m] for s in ps])
            tree, _loss = train_participants(
                unflatten(w, w0), x[sel], y[sel], np.asarray(ps),
                seed * 1000 + beta, cfg, local_iters, precision)
            tree = jax.tree.map(np.asarray, tree)
            rows[r] = np.stack([flatten({k: v[i] for k, v in tree.items()})
                                for i in range(len(ps))])
            if not first:
                first = {"participants": list(ps), "rows": tree,
                         "used": [s for (_t, s, _k) in sl.used]}
        flat = np.stack([rows[rk[0]][rk[1]] for (_t, _s, _e, rk)
                         in sl.models])
        sats = [mm[1] for mm in sl.models]
        groups = [[i] for i in range(len(sats))]
        new_d = {}
        if grouping is not None:
            new = [i for i, s in enumerate(sats)
                   if not grouping.known(s // N)]
            if new:
                new_d = orbit_distances([sats[i] for i in new], flat[new],
                                        sizes, w0_flat, N, server_precision)
                for o, d in new_d.items():
                    grouping.add(o, d)
            groups = [[i for i, s in enumerate(sats) if s // N in g]
                      for g in grouping.groups]
            groups = [g for g in groups if g]
        ws, base_w, gamma, stale_groups = rule.weights(
            [(s, sizes[s], mm[2]) for s, mm in zip(sats, sl.models)],
            beta, groups)
        w = contract(ws, flat, base_w, w, server_precision)
        first.setdefault("dists", new_d)
        commits.append(Commit(
            sl.t_agg, [(s, mm[2]) for s, mm in zip(sats, sl.models)],
            {s: float(v) for s, v in zip(sats, ws)}, float(base_w),
            float(gamma), int(stale_groups), groups, w))
    return Lap(schedule=slots, sizes=sizes, w0=w0, commits=commits, **first)
