"""Plain reference of a lap's first commits, from the seed alone.

It imports nothing of the program under test and takes nothing the program
made.  Everything it follows is stated by the configuration file (the
deployment: constellation, parameter-server coordinates, link, data,
training) and the mix file (the strategy's ``spec``: which parameter
server, which commit rule).  In straightforward numpy and ``jax.numpy``:

* the local model, found by the configuration's ``model_kind`` as
  ``chipbench/models/<model_kind>.py`` (its reference part): the seeded
  data and each satellite's shard, the initial model, and local training
  of every participant of a round from the reference's own global model;
* each round's schedule: Walker-delta orbits, an Earth-fixed parameter
  server, elevation gating on the ``dt_s`` grid, the link delay, the
  downlink with intra-orbit ISL relay and the uplink (Alg. 1);
* the commits: the rule ``chipbench/rules/<agg_mode>.py`` says when a round
  commits and with which weights; carried models re-enter later commits
  with the epoch they trained from, a satellite counts once (its newest
  model), and orbits are grouped by distance (rules that group).

A model kind's reference part provides, in numpy and ``jax.numpy`` alone:

``data(seed, cfg) -> (inputs, shards)``
    ``inputs``: a tuple of host arrays indexed by sample on axis 0;
    ``shards``: one index array per satellite.
``shard_rows(seed, cfg) -> int``
    the samples per satellite once shards are cut to the smallest (a shape
    of the program's compiled step; ``harness.run_seed`` matches it).
``init_params(seed, cfg) -> dict``
    the initial model, a dict of leaves.
``train_participants(w, inputs_sel, ids, epoch_seed, cfg, local_iters,
precision) -> (tree, losses)``
    every participant's local training from ``w``: ``inputs_sel`` is
    ``inputs`` gathered by participant (leading axis), ``tree`` the trained
    leaves with a leading participant axis; ``precision`` is
    ``"highest"`` (the reference) or ``"bfloat16"`` (the control).  A
    participant's trained model depends only on ``w``, its own inputs, its
    id and ``epoch_seed``, never on the other participants of the call: the
    reference trains a round in chunks (``chunk_size``).

Every file found by name (kinds, rules) is looked up under a ``root``: the
checkout the cell came from, the repository's by default.

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

Memory stays bounded by the blocks, not the participants.  A commit's
weights are, within one *block* of its models, in proportion to their data
sizes (the rule's ``BLOCKS``: ``"orbit"``, the models of one orbit trained
from one epoch; ``"model"``, each model alone), so eq. 14 and the grouping
distances need the trained models only through their size-weighted sums,
one per block.  Each trained chunk is folded at once, in float64, into the
sums of the blocks its models go to (orbit or model, epoch trained from,
the commit that takes them; a carried model's block lives until that
commit) and dropped: O((blocks + chunk) x N) floats, a chunk being as
many participants as ``CHUNK_BYTES`` holds (``chunk_size``).  The first
round is also folded into the blocks the check compares (``block_key``);
its single rows are kept only for a configuration whose program returns
its bank (``step_output`` ``"bank"``), whose check compares them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

R_EARTH = 6371.0e3
GM = 3.986004418e14
OMEGA_EARTH = 7.2921159e-5
C_LIGHT = 299_792_458.0
MIN_COMMITS = 3
MAX_COMMITS = 16
# host bytes for one trained chunk: its rows as float64 flats and the
# kind's float32 leaves, 12 bytes a parameter
CHUNK_BYTES = 1 << 30
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_module(path: str, name: str):
    """Import a file by its path, as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def found(kind: str, name: str, root: str = ROOT):
    """The file ``chipbench/<kind>/<name>.py`` under ``root``, imported."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise ValueError(f"no {kind} file {path}")
    return load_module(path, f"chipbench_{kind}_" + name.replace(".", "_"))


def load_rule(agg_mode: str, root: str = ROOT):
    """The commit rule ``rules/<agg_mode>.py``."""
    return found("rules", agg_mode, root)


def load_kind(model_kind: str, root: str = ROOT):
    """The local model ``models/<model_kind>.py``."""
    return found("models", model_kind, root)


def _precision(precision: str):
    """The server contractions' precision."""
    import jax
    return {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH}[precision]


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
class Block:
    """Size-weighted sum of trained models: ``sum`` = sum of size_i x_i
    over ``sats``, float64; ``size`` = sum of size_i."""
    sats: List[int]
    size: float
    sum: np.ndarray


@dataclasses.dataclass
class Commit:
    t_agg: float
    models: List[Tuple[int, int]]    # (sat, epoch trained from), in order
    weights: Dict[int, float]        # sat -> aggregation weight
    base_weight: float
    gamma: float
    stale_groups: int
    groups: List[List[int]]          # indices into ``models``
    change: Dict[str, float]         # per leaf: norm of (w - w0)
    w: Optional[np.ndarray] = None   # the global model after it (float64;
    #                                  kept for the first commit only)


@dataclasses.dataclass
class Lap:
    schedule: List[Slot]             # every commit of the lap
    participants: List[int]          # round 0's
    rows: Optional[Dict]             # round 0's trained rows (P, ...), or
    #                                  None where the check reads no rows
    used: List[int]                  # sat ids of the first commit, in order
    sizes: Dict[int, int]            # data size per satellite
    w0: Dict                         # host float32 leaves
    dists: Dict[int, float]          # first commit: orbit -> distance
    commits: List[Commit]            # the commits followed with models
    blocks: Dict[tuple, Block]       # round 0's, by ``block_key``


def block_key(sat: int, taken: bool, per_orbit: int, by_orbit: bool):
    """The block of a step's output that a trained model goes to: its orbit
    (a rule with ``BLOCKS`` ``"orbit"``) or the satellite itself, and
    whether the step's commit takes it (else it is carried, or never
    arrives)."""
    return (int(sat) // per_orbit if by_orbit else int(sat), bool(taken))


def change_norms(w_flat, w0_flat, slices) -> Dict[str, float]:
    """Per leaf, the norm of the change ``w - w0`` (float64)."""
    return {k: float(np.linalg.norm(np.asarray(w_flat[sl], np.float64)
                                    - w0_flat[sl]))
            for k, sl in slices.items()}


def _weighted_sum(wv, rows, precision: str) -> np.ndarray:
    if precision == "highest":
        return np.asarray(wv, np.float64) @ np.asarray(rows, np.float64)
    import jax.numpy as jnp
    return np.asarray(jnp.dot(jnp.asarray(wv, jnp.float32),
                              jnp.asarray(rows, jnp.float32),
                              precision=_precision(precision)), np.float64)


def fold(blocks: Dict, keys: List, sats: List[int], flat: np.ndarray,
         sizes: Dict[int, int], precision: str = "highest") -> None:
    """Add the rows ``flat`` (one per satellite of ``sats``) into
    ``blocks``, row ``i`` under ``keys[i]`` (None: into none), each
    weighted by its satellite's data size; float64 on the host, or the
    device at ``precision`` for a control."""
    for key in dict.fromkeys(k for k in keys if k is not None):
        idx = [i for i, k in enumerate(keys) if k == key]
        wv = np.array([float(sizes[sats[i]]) for i in idx])
        part = _weighted_sum(wv, flat[idx], precision)
        b = blocks.get(key)
        if b is None:
            blocks[key] = Block([sats[i] for i in idx], float(wv.sum()), part)
        else:
            b.sats += [sats[i] for i in idx]
            b.size += float(wv.sum())
            b.sum += part


def first_blocks(rows: Dict, participants: List[int], used: List[int],
                 sizes: Dict[int, int], per_orbit: int,
                 by_orbit: bool) -> Dict[tuple, Block]:
    """Round 0's blocks (``block_key``) from its trained rows."""
    flat = np.stack([flatten({k: v[i] for k, v in rows.items()})
                     for i in range(len(participants))])
    out: Dict[tuple, Block] = {}
    fold(out, [block_key(s, s in used, per_orbit, by_orbit)
               for s in participants], list(participants), flat, sizes)
    return out


def contract(ws, rows, base_w: float, w_flat, precision: str):
    """eq. 14: ``base_w * w + sum_i ws_i rows_i``; float64 on the host for
    the reference, the device at ``precision`` for a control.  ``rows``
    are trained models or block sums."""
    if precision == "highest":
        out = float(base_w) * np.asarray(w_flat, np.float64)
        for c, r in zip(ws, rows):
            out += float(c) * np.asarray(r, np.float64)
        return out
    import jax.numpy as jnp
    out = (jnp.float32(base_w) * jnp.asarray(w_flat, jnp.float32)
           + jnp.dot(jnp.asarray(ws, jnp.float32),
                     jnp.asarray(np.stack(rows), jnp.float32),
                     precision=_precision(precision)))
    return np.asarray(out, np.float64)


def block_distances(orbit_blocks, w0_flat) -> Dict[int, float]:
    """Grouping distances: per orbit, its partial model (the size-weighted
    mean of its models, from the blocks ``(orbit, Block)`` it holds)
    against ``w0`` (Euclidean), in the order the orbits first appear."""
    acc: Dict[int, list] = {}
    for o, b in orbit_blocks:
        if o in acc:
            acc[o][0] = acc[o][0] + b.sum
            acc[o][1] += b.size
        else:
            acc[o] = [b.sum, b.size]
    w0 = np.asarray(w0_flat, np.float64)
    return {o: float(np.linalg.norm(s / n - w0)) for o, (s, n) in acc.items()}


def orbit_distances(sats, flat_rows, sizes, w0_flat, per_orbit: int,
                    precision: str = "highest") -> Dict[int, float]:
    """Grouping distances from single rows: each orbit's models folded into
    one block (at ``precision``), then ``block_distances``."""
    blocks: Dict[int, Block] = {}
    fold(blocks, [s // per_orbit for s in sats], list(sats),
         np.asarray(flat_rows), sizes, precision)
    return block_distances(blocks.items(), w0_flat)


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


def schedule(cfg: Dict, spec: Dict, bits: float, root: str = ROOT
             ) -> Tuple[List[Slot], List[List[int]]]:
    """Every commit of a lap, and each round's participants: one round in
    flight, opened at the previous round's last commit; models carried
    past a commit re-enter the first commit after they land."""
    rule = load_rule(spec["agg_mode"], root)
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


def initial_model(cfg: Dict, seed: int, root: str = ROOT) -> Dict:
    """The reference's initial model for run seed ``seed``: host leaves."""
    kind = load_kind(cfg["model_kind"], root)
    return {k: np.asarray(v) for k, v in kind.init_params(seed, cfg).items()}


def _train_chunk(kind, w_tree, inputs, shards, m, part, epoch_seed, cfg,
                 local_iters, precision, keep):
    """One chunk's local training: its rows as float64 flats, and the
    trained leaves (host float32 copies) where ``keep``.  What the kind
    returned is dropped on return."""
    import jax
    sel = np.stack([shards[s][:m] for s in part])
    tree, _loss = kind.train_participants(
        w_tree, tuple(a[sel] for a in inputs), np.asarray(part), epoch_seed,
        cfg, local_iters, precision)
    tree = jax.tree.map(np.asarray, tree)
    flat = np.stack([flatten({k: v[i] for k, v in tree.items()})
                     for i in range(len(part))])
    kept = {k: np.array(v) for k, v in tree.items()} if keep else None
    return flat, kept


def chunk_size(n_params: int, participants: int) -> int:
    """Participants trained at once: the whole round where its rows fit
    ``CHUNK_BYTES``, else as many as fit, at least one."""
    return max(1, min(participants, CHUNK_BYTES // (12 * n_params)))


def lap(cfg: Dict, spec: Dict, seed: int, *, local_iters: int,
        precision: str = "highest", server_precision: str = "highest",
        root: str = ROOT, w0: Optional[Dict] = None,
        shard_of: Optional[Dict[int, int]] = None,
        chunk: Optional[int] = None) -> Lap:
    """The reference's lap for ``seed`` (a run seed) under the mix's
    ``spec`` (``ps_scenario``, ``agg_mode``, ``use_isl``, ``num_groups``):
    its whole schedule, and its models, weights and global model for the
    first commits, up to the first that holds a stale model.  The model
    kind and the commit rule are found under ``root``.  ``w0``: the initial
    model where it is already made (``initial_model``).  ``shard_of``: a
    planted fault, the satellite whose shard a participant trains on.
    ``chunk``: participants trained at once, ``chunk_size``'s by
    default."""
    kind = load_kind(cfg["model_kind"], root)
    rule = load_rule(spec["agg_mode"], root)
    N = cfg["constellation"]["sats_per_orbit"]
    by_orbit = rule.BLOCKS == "orbit"
    inputs, shards = kind.data(seed, cfg)
    sizes = {s: len(sh) for s, sh in enumerate(shards)}
    m = min(sizes.values())
    if shard_of:
        shards = [shards[shard_of.get(s, s)] for s in range(len(shards))]
    if w0 is None:
        w0 = initial_model(cfg, seed, root)
    w0_flat = flatten(w0)
    slices = leaf_slices(w0)
    slots, rounds = schedule(cfg, spec, 32.0 * w0_flat.size, root)
    if len(slots) < MIN_COMMITS:
        raise ValueError(f"the lap has {len(slots)} commits, fewer than "
                         f"the {MIN_COMMITS} the check follows")
    stale = [k for k, sl in enumerate(slots)
             if any(mm[2] < k for mm in sl.models)]
    n = min(max(MIN_COMMITS, stale[0] + 1 if stale else 0), MAX_COMMITS,
            len(slots))
    grouping = Grouping(spec["num_groups"]) if rule.GROUPING else None
    keep_rows = cfg.get("step_output", "bank") == "bank"
    share = server_precision == "highest"

    def group(s):
        return s // N if by_orbit else s

    # the commit that takes each trained model, among those followed
    taker = {mm[3]: j for j, sl in enumerate(slots[:n]) for mm in sl.models}
    used0 = [s for (_t, s, _k) in slots[0].used]
    w = w0_flat
    sums: Dict[tuple, Block] = {}        # (group, epoch, taker) -> Block
    blocks: Dict[tuple, Block] = {}      # round 0's, by block_key
    parts: List[Dict] = []
    dists: Dict[int, float] = {}
    commits: List[Commit] = []
    for beta, sl in enumerate(slots[:n]):
        r, _b = sl.train
        if r >= 0:
            ps = rounds[r]
            w_tree = unflatten(w, w0)
            step = chunk or chunk_size(w0_flat.size, len(ps))
            for a in range(0, len(ps), step):
                part = ps[a:a + step]
                flat, kept = _train_chunk(
                    kind, w_tree, inputs, shards, m, part, seed * 1000 + beta,
                    cfg, local_iters, precision, keep_rows and r == 0)
                keys = [(group(s), beta, taker[(r, a + i)])
                        if (r, a + i) in taker else None
                        for i, s in enumerate(part)]
                fold(sums, keys, part, flat, sizes, server_precision)
                if r == 0:
                    fold(blocks, [None if share and s in used0 else
                                  block_key(s, s in used0, N, by_orbit)
                                  for s in part], part, flat, sizes)
                    if kept is not None:
                        parts.append(kept)
                del flat, kept
            if r == 0 and share:
                for (g, _e, j), b in sums.items():
                    if j == 0:
                        blocks[(g, True)] = b
        sats = [mm[1] for mm in sl.models]
        orbit_of = {key: (key[0] if by_orbit else key[0] // N)
                    for key in sums if key[2] == beta}
        groups = [[i] for i in range(len(sats))]
        new_d = {}
        if grouping is not None:
            new = list(dict.fromkeys(s // N for s in sats
                                     if not grouping.known(s // N)))
            if new:
                got = block_distances([(o, sums[key])
                                       for key, o in orbit_of.items()
                                       if o in new], w0_flat)
                new_d = {o: got[o] for o in new}
                for o, d in new_d.items():
                    grouping.add(o, d)
            groups = [[i for i, s in enumerate(sats) if s // N in g]
                      for g in grouping.groups]
            groups = [g for g in groups if g]
        ws, base_w, gamma, stale_groups = rule.weights(
            [(s, sizes[s], mm[2]) for s, mm in zip(sats, sl.models)],
            beta, groups)
        ws = np.asarray(ws, np.float64)
        if sum(len(sums[key].sats) for key in orbit_of) != len(sats):
            raise AssertionError(f"commit {beta} takes models of no block")
        cs, rows = [], []
        for key in orbit_of:
            idx = [i for i, mm in enumerate(sl.models)
                   if (group(mm[1]), mm[2]) == key[:2]]
            if sorted(sats[i] for i in idx) != sorted(sums[key].sats):
                raise AssertionError(f"block {key} holds other models than "
                                     f"commit {beta} takes")
            sz = np.array([float(sizes[sats[i]]) for i in idx])
            c = float(ws[idx].sum() / sz.sum())
            if np.max(np.abs(ws[idx] - c * sz)) > 1e-9 * np.max(np.abs(ws)):
                raise ValueError(f"rule {spec['agg_mode']!r}: the weights of "
                                 f"block {key} are not in proportion to the "
                                 "data sizes; its BLOCKS must be 'model'")
            cs.append(c)
            rows.append(sums.pop(key).sum)
        w = contract(cs, rows, base_w, w, server_precision)
        del rows
        if beta == 0:
            dists = new_d
        commits.append(Commit(
            sl.t_agg, [(s, mm[2]) for s, mm in zip(sats, sl.models)],
            {s: float(v) for s, v in zip(sats, ws)}, float(base_w),
            float(gamma), int(stale_groups), groups,
            change_norms(w, w0_flat, slices), w if beta == 0 else None))
    rows0 = ({k: np.concatenate([p[k] for p in parts]) for k in parts[0]}
             if parts else None)
    return Lap(schedule=slots, participants=list(rounds[0]), rows=rows0,
               used=used0, sizes=sizes, w0=w0, dists=dists, commits=commits,
               blocks=blocks)
