"""Bring-up check: the AsyncFLEO simulator end to end on a TPU.

    python chip_smoke.py              # one chip: phases 1-5
    python chip_smoke.py --chips 4    # one host's four chips: phase 6 only

One process runs the phases in order.  A failed phase raises: the script
exits non-zero and prints no verdict.

1. device        JAX's default device must be a TPU; there is no CPU
                 fallback.
2. compile cache ``repro.compile_cache``, before the first compile.
3. kernels       ``fed_agg`` at the paper's bank shape (C=64, the S=40
                 participant bucket, by N=206,922) and ``pairwise_dist`` at
                 orbit-partial shape (M=9 by N), compiled for the chip,
                 against their jnp oracles.
4. paper run     ``launch.fl_train.build_run``: the S=40 paper
                 constellation, ``MNIST_CNN`` at its published widths,
                 the paper non-IID partition, 30 local iterations, AsyncFLEO
                 on the event-driven runtime, 3 epochs.
5. precision     one epoch of that run with the XLA and with the ``fed_agg``
                 eq. 14 contraction, against a float32 reference of the same
                 epoch on the host CPU; and each program's eq. 14 contraction
                 and grouping distances against a float64 recomputation
                 from the bank that program trained.
6. mesh          (``--chips 4``) one fused epoch with the bank sharded over
                 a 4-chip "data" mesh, against the same epoch on one chip.

The last line of stdout is one JSON object naming the device JAX reports.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STRATEGY = "asyncfleo-hap"
EPOCHS = 3
LOCAL_ITERS = 30
PAPER_PARAMS = 206_922            # MNIST_CNN as published (conv 16/32, 128)
BANK_ROWS = 64                    # S=40 participants, pow2-bucketed
ORBIT_PARTIALS = 9
# Relative error max|x - ref| / max|ref| allowed.  The server's math (the
# kernels, eq. 14, grouping distances) runs at HIGHEST precision: f32
# rounding is ~1e-7, one bf16 pass measured 3e-4 to 3e-3 on a v5e chip.
# Local training keeps XLA's default TPU precision, so a whole epoch
# drifts from the float32 CPU reference (4e-4 measured on a v5e chip).
KERNEL_RTOL = 1e-5                # a Pallas kernel vs its oracle, same data
SERVER_RTOL = 1e-4                # eq. 14 / grouping distances vs float64
EPOCH_RTOL = 5e-3                 # a chip epoch vs the float32 CPU epoch
MESH_RTOL = 5e-3                  # the 4-chip epoch vs the 1-chip epoch


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / max(np.max(np.abs(ref)), 1e-30))


def flat(tree) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(leaf, np.float64))
                           for leaf in jax.tree.leaves(tree)])


class Capture:
    """Stands in for an ``EpochStepProgram``'s jitted step: records what
    each dispatch contracted (host copies, taken before the donated model
    is consumed) and its outputs, and the compiled HLO of the first one."""

    def __init__(self, prog, want_hlo: bool = False):
        self.fn = prog._step
        prog._step = self
        self.want_hlo = want_hlo
        self.hlo = None
        self.inputs_layout = None     # (placed, compiled-for) per leaf
        self.calls = []

    def __call__(self, *args):
        if self.want_hlo and self.hlo is None:
            compiled = self.fn.lower(*args).compile()
            self.hlo = compiled.as_text()
            self.inputs_layout = [
                (leaf.sharding, want, leaf.ndim) for leaf, want in zip(
                    jax.tree.leaves(args[2]),
                    jax.tree.leaves(compiled.input_shardings[0][2]))]
        (w, carry, _inputs, _ids, _seed, wv_bank, wv_carry, base_w,
         dw_row, dw_seg, kpad, blocked_m, dw_carry, ref) = args
        host = {k: np.asarray(v, np.float64) for k, v in dict(
            w=w, carry=carry, wv_bank=wv_bank, wv_carry=wv_carry,
            base_w=base_w, dw_row=dw_row, dw_carry=dw_carry, ref=ref).items()}
        host["dw_seg"] = np.asarray(dw_seg)
        out = self.fn(*args)
        self.calls.append((host, int(kpad), int(blocked_m), out))
        return out


def server_reference(host, kpad: int, blocked_m: int, stack):
    """Float64 eq. 14 contraction and new-orbit grouping distances of one
    dispatch, recomputed from the bank the program trained."""
    stack = np.asarray(stack, np.float64)
    new_w = (host["base_w"] * host["w"] + host["wv_bank"] @ stack
             + host["wv_carry"] @ host["carry"])
    if not kpad:
        return new_w, np.zeros(0)
    rows = stack.shape[0]
    seg = (np.arange(rows) // blocked_m) if blocked_m else host["dw_seg"]
    w_mat = np.zeros((kpad + 1, rows))
    w_mat[seg, np.arange(rows)] = host["dw_row"]
    pm = (w_mat @ stack)[:kpad] + host["dw_carry"] @ host["carry"]
    return new_w, np.linalg.norm(pm - host["ref"][None, :], axis=1)


def paper_run(cfg, spec, sim_cfg, epochs: int, *, w0=None,
              local_iters: int = LOCAL_ITERS, want_hlo: bool = False):
    """The launcher's run, with its fused program's dispatches captured."""
    from repro.core.epoch_step import make_epoch_program
    from repro.launch.fl_train import build_run

    sim, w_init = build_run(cfg, spec, sim_cfg, local_iters=local_iters)
    w0 = w_init if w0 is None else w0
    prog = make_epoch_program(sim.trainer, w0, mesh=sim_cfg.mesh,
                              use_kernel=spec.use_agg_kernel)
    cap = Capture(prog, want_hlo)
    hist = sim.run(w0, max_epochs=epochs)
    check(len(cap.calls) > 0, "the run did not dispatch through the fused "
                              "program")
    return sim, w0, prog, cap, hist


def phase_device(chips: int):
    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX's default device is "
                                 f"{dev.platform}")
    check(count >= chips, f"--chips {chips} needs {chips} devices, "
                          f"JAX sees {count}")
    return dev


def phase_compile_cache():
    from repro.compile_cache import configure_compile_cache
    print(f"compile cache: {configure_compile_cache()}", flush=True)


def phase_kernels(n: int = PAPER_PARAMS):
    from repro.kernels.fed_agg.ops import fed_agg
    from repro.kernels.fed_agg.ref import fed_agg_flat_ref
    from repro.kernels.pairwise_dist.ops import pairwise_dist
    from repro.kernels.pairwise_dist.ref import pairwise_dist_sq_ref

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(0), 4)
    stack = 0.1 * jax.random.normal(k1, (BANK_ROWS, n))
    gamma = jax.random.uniform(k2, (BANK_ROWS,)) / BANK_ROWS
    base = 0.1 * jax.random.normal(k3, (n,))
    got = fed_agg(stack, gamma, base, 0.5, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = fed_agg_flat_ref(stack, gamma, base, 0.5)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    rel = rel_err(got, want)
    print(f"kernel fed_agg ({BANK_ROWS}, {n}): max abs err {err!r} "
          f"rel {rel!r}", flush=True)
    check(rel <= KERNEL_RTOL, f"fed_agg strays from its oracle: {rel}")

    parts = 0.1 * jax.random.normal(k4, (ORBIT_PARTIALS, n))
    got = pairwise_dist(parts, squared=True, interpret=False)
    want = pairwise_dist_sq_ref(parts)
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    rel = rel_err(got, want)
    print(f"kernel pairwise_dist ({ORBIT_PARTIALS}, {n}): max abs err "
          f"{err!r} rel {rel!r}", flush=True)
    check(rel <= KERNEL_RTOL, f"pairwise_dist strays from its oracle: {rel}")


def phase_paper_run(cfg, dev, *, epochs: int = EPOCHS,
                    local_iters: int = LOCAL_ITERS):
    from repro.core import SimConfig
    from repro.fl import get_strategy

    sim, w0, prog, cap, hist = paper_run(
        cfg, get_strategy(STRATEGY), SimConfig(event_driven=True), epochs,
        local_iters=local_iters)
    print(f"paper run: {STRATEGY} S={sim.constellation.num_sats} "
          f"{cfg.name} params={prog.spec.num_params} "
          f"local_iters={local_iters} epochs={len(hist)}", flush=True)
    for r in hist:
        print(f"  epoch {r.epoch} sim {r.time_s / 3600:.3f} h "
              f"acc {r.accuracy!r} models {r.num_models} "
              f"gamma {r.gamma!r}", flush=True)
    losses = [np.asarray(out[3]) for (_h, _k, _b, out) in cap.calls]
    print(f"  losses per dispatch: {[float(l.mean()) for l in losses]}")
    print(f"  dispatches {prog.dispatches}, fallback dispatches "
          f"{prog.fallback_dispatches}, traces {prog.traces}", flush=True)
    check(len(hist) == epochs, f"{len(hist)} of {epochs} epochs committed")
    check(all(np.isfinite(r.accuracy) for r in hist), "non-finite accuracy")
    check(all(np.all(np.isfinite(l)) for l in losses), "non-finite loss")
    check(prog.dispatches > 0, "the fused program never dispatched")
    final = sim.global_model()
    check(np.max(np.abs(flat(final) - flat(w0))) > 0,
          "the global model did not change")
    leaves = jax.tree.leaves(final)
    check(all(leaf.devices() == {dev} for leaf in leaves),
          f"the global model is not on {dev}")
    return prog.spec.num_params


def phase_precision(cfg, *, local_iters: int = LOCAL_ITERS):
    from repro.core import SimConfig
    from repro.fl import get_strategy

    base = get_strategy(STRATEGY)
    chip = {}
    for use_kernel in (False, True):
        spec = dataclasses.replace(base, use_agg_kernel=use_kernel)
        sim, w0, _p, cap, _h = paper_run(
            cfg, spec, SimConfig(event_driven=True), 1,
            w0=chip[False][1] if chip else None, local_iters=local_iters,
            want_hlo=use_kernel)
        chip[use_kernel] = (sim, w0, cap)
    w0 = chip[False][1]
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        ref_sim, _w, _p, ref_cap, _h = paper_run(
            cfg, base, SimConfig(event_driven=True), 1, w0=w0,
            local_iters=local_iters)
        w_ref = flat(ref_sim.global_model())
        loss_ref = np.asarray(ref_cap.calls[0][3][3])

    check("tpu_custom_call" in chip[True][2].hlo,
          "the kernel run's compiled program holds no tpu_custom_call")
    print("kernel run: compiled program holds tpu_custom_call", flush=True)
    for use_kernel, (sim, _w0, cap) in chip.items():
        name = "fed_agg kernel" if use_kernel else "XLA contraction"
        host, kpad, blocked_m, (new_w, stack, dists, losses) = cap.calls[0]
        ref_w, ref_d = server_reference(host, kpad, blocked_m, stack)
        k = len(ref_d)
        e_w = rel_err(new_w, ref_w)
        e_d = rel_err(np.asarray(dists)[:k], ref_d) if k else 0.0
        e_epoch = rel_err(flat(sim.global_model()), w_ref)
        e_loss = rel_err(losses, loss_ref)
        print(f"{name}: eq14 rel err {e_w!r}, grouping distances ({k}) "
              f"rel err {e_d!r}; epoch vs float32 CPU reference: model "
              f"rel err {e_epoch!r}, losses rel err {e_loss!r}", flush=True)
        check(e_w <= SERVER_RTOL, f"{name}: eq. 14 contraction strays "
                                  f"{e_w} from float64")
        check(e_d <= SERVER_RTOL, f"{name}: grouping distances stray "
                                  f"{e_d} from float64")
        check(e_epoch <= EPOCH_RTOL, f"{name}: epoch strays {e_epoch} "
                                     f"from the CPU reference")


def phase_mesh(cfg, *, chips: int = 4, local_iters: int = LOCAL_ITERS):
    from repro.core import SimConfig
    from repro.fl import get_strategy
    from repro.launch.mesh import make_data_mesh

    spec = get_strategy(STRATEGY)
    mesh = make_data_mesh()
    check(mesh.devices.size == chips, f"mesh over {mesh.devices.size} "
                                      f"devices, want {chips}")
    sim_m, w0, _p, cap_m, _h = paper_run(
        cfg, spec, SimConfig(event_driven=True, mesh=mesh), 1,
        local_iters=local_iters, want_hlo=True)
    sim_1, _w, _p, _c, _h = paper_run(
        cfg, spec, SimConfig(event_driven=True), 1, w0=w0,
        local_iters=local_iters)
    stack = cap_m.calls[0][3][1]
    rows = sorted({s.data.shape[0] for s in stack.addressable_shards})
    devs = len(stack.sharding.device_set)
    err = rel_err(flat(sim_m.global_model()), flat(sim_1.global_model()))
    print(f"mesh: bank {tuple(stack.shape)} over {devs} devices, rows per "
          f"device {rows}; new global model vs one chip rel err {err!r}",
          flush=True)
    check(devs == chips, f"the bank spans {devs} devices, want {chips}")
    check(rows == [stack.shape[0] // chips],
          f"rows per device {rows}, want {stack.shape[0] // chips}")
    check(err <= MESH_RTOL, f"the mesh epoch strays {err} from one chip")
    # the simulator puts the commit's inputs in the layout the program
    # compiled for, so the call reshards nothing
    spread = [len(placed.device_set) for placed, _w, _n in cap_m.inputs_layout]
    print(f"mesh: inputs placed over {spread} devices", flush=True)
    check(all(placed.is_equivalent_to(want, ndim)
              for placed, want, ndim in cap_m.inputs_layout),
          "the inputs are not in the layout the mesh program takes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-chip mesh phase")
    args = ap.parse_args(argv)
    dev = phase_device(args.chips)
    phase_compile_cache()
    from repro.configs import MNIST_CNN
    if args.chips == 4:
        phase_mesh(MNIST_CNN)
    else:
        phase_kernels()
        params = phase_paper_run(MNIST_CNN, dev)
        check(params == PAPER_PARAMS, f"MNIST_CNN has {params} parameters, "
                                      f"published {PAPER_PARAMS}")
        phase_precision(MNIST_CNN)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
