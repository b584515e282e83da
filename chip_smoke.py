#!/usr/bin/env python3
"""Bring-up check on the TPU: train, serve and the collectives, end to end.

    python chip_smoke.py             # one chip: train, serve, collectives
    python chip_smoke.py --chips 4   # four chips: the multi-chip collectives
                                     # and a 2x2 train step, nothing else

The default run drives the package's own entry points on one chip:

* train: a few steps of qwen3-0.6b at its published widths through
  ``repro.launch.train`` (``make_train_step`` + ``train_loop.train``, mode
  ``hier``, float32).  The losses must be finite and must fall.
* serve: a few requests through ``repro.launch.serve``
  (``ContinuousBatchingScheduler``) at the same widths.  Every request
  must get all of its tokens.
* collectives: allgather and broadcast under ``naive``, ``hier`` and
  ``shared`` must equal their input.  ``ag_matmul(use_kernel=True)`` must
  compile to a Pallas TPU kernel (``tpu_custom_call`` in its HLO) and match
  ``x @ w``.

``--chips 4`` builds the 1x4, 2x2, 4x1 and 1x(2x2) clusters on the four
chips.  On each it runs allgather, broadcast, allreduce, reduce_scatter and
alltoall under every exact scheme and compares each result with a host
NumPy reference.  It checks the paper's C1 from the real output shards:
per node, ``shared`` holds ``naive``'s resident bytes divided by the ranks
per node.  Last, it runs one qwen3-0.6b train step on 2x2 under ``hier``
and under ``naive``, whose losses must agree.

Every earlier line of output is a measurement or a check.  The last line
is one JSON object naming the device.  With no TPU, or fewer chips than
asked for, the script exits non-zero and prints no result.  A failed check
raises, so the script exits non-zero.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

import numpy as np

ARCH = "qwen3-0.6b"


def say(phase: str, **kv) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def peak_bytes() -> int:
    """The highest ``peak_bytes_in_use`` over the devices."""
    import jax
    return max(d.memory_stats()["peak_bytes_in_use"] for d in jax.devices())


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def phase_train(steps: int = 5, batch: int = 8, seq: int = 128) -> None:
    from repro.launch import train as launch_train

    rep = launch_train.main(["--arch", ARCH, "--steps", str(steps),
                             "--batch", str(batch), "--seq", str(seq),
                             "--mode", "hier"])
    losses, times = list(rep.losses), list(rep.step_times)
    del rep                                  # drop the train state
    gc.collect()
    steady = times[1:]
    say("train", arch=ARCH, steps=steps, batch=batch, seq=seq,
        first_step_s_incl_compile=times[0],
        step_s=steady, median_step_s=float(np.median(steady)),
        tokens_per_s=batch * seq / float(np.median(steady)),
        peak_bytes_in_use=peak_bytes())
    say("train", losses=losses)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


def phase_serve(requests: int = 8, slots: int = 4, max_new: int = 8) -> None:
    from repro.configs import get_config
    from repro.launch import serve as launch_serve

    t0 = time.perf_counter()
    results = launch_serve.main(["--arch", ARCH, "--requests", str(requests),
                                 "--slots", str(slots), "--prompt-max", "16",
                                 "--max-new", str(max_new)])
    wall = time.perf_counter() - t0
    vocab = get_config(ARCH).vocab
    toks = [r.tokens for r in results.values()]
    say("serve", arch=ARCH, answered=len(results), requests=requests,
        tokens=sum(t.size for t in toks), wall_s_incl_compile=wall,
        peak_bytes_in_use=peak_bytes())
    check(len(results) == requests, f"{len(results)}/{requests} answered")
    for t in toks:
        check(t.shape == (1, max_new), f"request got tokens {t.shape}")
        check(bool(np.all((t >= 0) & (t < vocab))), "token out of vocab")
    gc.collect()


def phase_collectives_one_chip() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comm import Communicator
    from repro.substrate import VirtualCluster

    vc = VirtualCluster(pods=1, chips=1)
    comm = Communicator.from_cluster(vc)
    x = vc.rank_major_input(m=64, extra=128)
    want = np.asarray(x)
    for scheme in ("naive", "hier", "shared"):
        if scheme == "shared":
            def ag(v):
                return comm.allgather(v, scheme="shared").read_rank_order()

            def bc(v):
                return comm.broadcast(v, scheme="shared").read()
        else:
            def ag(v, s=scheme):
                return comm.allgather(v, scheme=s)

            def bc(v, s=scheme):
                return comm.broadcast(v, scheme=s)
        got_ag = np.asarray(vc.run(ag, x, out_specs=P(None)))
        got_bc = np.asarray(vc.run(bc, x, out_specs=P(None)))
        np.testing.assert_array_equal(got_ag, want)
        np.testing.assert_array_equal(got_bc, want)
        say("collectives", cluster=vc.label, scheme=scheme,
            allgather="exact", broadcast="exact")

    # the fused collective-matmul through the Pallas kernel, at qwen3-0.6b's
    # 1024 x 3072 projection
    rng = np.random.default_rng(0)
    xm = rng.normal(size=(256, 1024)).astype(np.float32)
    wm = rng.normal(size=(1024, 3072)).astype(np.float32) / 32
    fn = jax.jit(vc.smap(lambda a, w: comm.ag_matmul(a, w, use_kernel=True),
                         in_specs=(P(), vc.spec), out_specs=P()))
    t0 = time.perf_counter()
    compiled = fn.lower(jnp.asarray(xm), jnp.asarray(wm)).compile()
    compile_s = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()
    got = np.asarray(compiled(jnp.asarray(xm), jnp.asarray(wm)))
    ref = xm.astype(np.float64) @ wm.astype(np.float64)
    err = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    say("collectives", op="ag_matmul(use_kernel=True)", shape="256x1024x3072",
        compile_s=compile_s, tpu_custom_call=kernel, rel_err=err)
    check(kernel, "ag_matmul(use_kernel=True) has no tpu_custom_call: the "
                  "Pallas kernel fell back to interpret mode")
    check(err < 1e-2, f"ag_matmul kernel rel err {err}")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def four_chip_clusters():
    from repro.substrate import VirtualCluster
    return (VirtualCluster(pods=1, chips=4),
            VirtualCluster(pods=2, chips=2),
            VirtualCluster(pods=4, chips=1),
            VirtualCluster(pods=1, chips=4, fast_axis=("dp", "tp"),
                           fast_shape=(2, 2), slow_axis="pod"))


def _exact_schemes(family: str) -> list[str]:
    from repro.comm import registry
    return [s.name for s in registry.schemes_for(family)
            if s.precision == "exact"]


def _node0_bytes(vc, out) -> int:
    devs = vc.mesh.devices
    if vc.pods > 1:
        devs = devs[(0,) * len(vc.slow_names)]
    node0 = set(devs.flatten().tolist())
    return sum(sh.data.nbytes for sh in out.addressable_shards
               if sh.device in node0)


def collectives_on(vc) -> None:
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.comm import Communicator

    comm = Communicator.from_cluster(vc)
    R = vc.num_devices
    rng = np.random.default_rng(R + vc.pods)
    m, e = 64, 128
    x = jnp.asarray(rng.normal(size=(R * m, e)).astype(np.float32))
    xs = np.asarray(x).reshape(R, m, e)
    root = R - 2
    close = dict(rtol=1e-5, atol=1e-5)
    resident: dict[tuple[str, str], int] = {}
    checked = []

    for s in _exact_schemes("psum"):           # allreduce
        shared = s == "shared"
        got = vc.run(lambda v, s=s: (comm.allreduce(v, scheme=s).read()
                                     if shared else
                                     comm.allreduce(v, scheme=s)),
                     x, out_specs=P(None))
        np.testing.assert_allclose(np.asarray(got)[:m], xs.sum(0), **close)
        held = vc.run(lambda v, s=s: (comm.allreduce(v, scheme=s).shard
                                      if shared else
                                      comm.allreduce(v, scheme=s))[None], x)
        resident[("allreduce", s)] = _node0_bytes(vc, held)
        checked.append(f"allreduce/{s}")

    for s in _exact_schemes("allgather"):
        shared = s == "shared"
        got = vc.run(lambda v, s=s: (
            comm.allgather(v, scheme=s).read_rank_order() if shared
            else comm.allgather(v, scheme=s)), x, out_specs=P(None))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
        held = vc.run(lambda v, s=s: (comm.allgather(v, scheme=s).shard
                                      if shared else
                                      comm.allgather(v, scheme=s))[None], x)
        resident[("allgather", s)] = _node0_bytes(vc, held)
        checked.append(f"allgather/{s}")

    msg = jnp.asarray(xs)                      # (R, m, e): rank r sends xs[r]
    for s in _exact_schemes("broadcast"):
        shared = s == "shared"
        got = vc.run(lambda v, s=s: (
            comm.broadcast(v[0], root=root, scheme=s).read() if shared
            else comm.broadcast(v[0], root=root, scheme=s))[None], msg)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.broadcast_to(xs[root], xs.shape))
        held = vc.run(lambda v, s=s: (
            comm.broadcast(v[0], root=root, scheme=s).shard if shared
            else comm.broadcast(v[0], root=root, scheme=s))[None], msg)
        resident[("broadcast", s)] = _node0_bytes(vc, held)
        checked.append(f"broadcast/{s}")

    flat = jnp.asarray(rng.normal(size=(R, 4 * R * e)).astype(np.float32))
    for s in _exact_schemes("reduce_scatter"):
        if s == "shared":
            got = vc.run(lambda v: comm.reduce_scatter(
                v[0], scheme="shared").read(), flat,
                in_specs=(vc.spec,), out_specs=P(None))
        else:
            got = vc.run(lambda v, s=s: comm.reduce_scatter(v[0], scheme=s),
                         flat, in_specs=(vc.spec,),
                         out_specs=P(vc.axis_names))
        np.testing.assert_allclose(np.asarray(got), np.asarray(flat).sum(0),
                                   **close)
        checked.append(f"reduce_scatter/{s}")

    a2a = jnp.asarray(rng.normal(size=(R * R * e,)).astype(np.float32))
    want = np.asarray(a2a).reshape(R, R, e).transpose(1, 0, 2).reshape(R, -1)
    for s in _exact_schemes("alltoall"):
        got = vc.run(lambda v, s=s: comm.alltoall(v, scheme=s), a2a)
        np.testing.assert_array_equal(np.asarray(got).reshape(R, -1), want)
        checked.append(f"alltoall/{s}")

    say("collectives", cluster=vc.label, matched_numpy=",".join(checked))
    # C1: one copy per node — shared holds naive's bytes / ranks_per_node
    for fam in ("allgather", "broadcast", "allreduce"):
        naive, shared = resident[(fam, "naive")], resident[(fam, "shared")]
        say("C1", cluster=vc.label, family=fam, naive_node_bytes=naive,
            shared_node_bytes=shared, ranks_per_node=vc.chips)
        check(naive == shared * vc.chips,
              f"C1 broke on {vc.label}/{fam}: {naive} != {shared}*{vc.chips}")
        for s in _exact_schemes("psum" if fam == "allreduce" else fam):
            if s != "shared":
                check(resident[(fam, s)] == naive,
                      f"{fam}/{s} holds {resident[(fam, s)]} B, naive {naive}")


def train_step_2x2(global_batch: int = 8, seq: int = 128) -> None:
    import jax

    from repro.configs import get_config
    from repro.data.synthetic import DataConfig, SyntheticLM
    from repro.runtime.steps import make_cluster_train_step
    from repro.substrate import VirtualCluster

    cfg = get_config(ARCH)
    vc = VirtualCluster(pods=2, chips=2)
    batch = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                   global_batch=global_batch)).next_batch()
    loss = {}
    for mode in ("hier", "naive"):
        bundle = make_cluster_train_step(cfg, vc, mode=mode,
                                         global_batch=global_batch)
        state = bundle.init_state(0)
        step = jax.jit(bundle.fn, donate_argnums=(0,))
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss[mode], gnorm = float(metrics["loss"]), float(metrics["gnorm"])
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        second = time.perf_counter() - t0
        say("train_2x2", arch=ARCH, mode=mode, loss=loss[mode], gnorm=gnorm,
            first_step_s_incl_compile=first, second_step_s=second,
            peak_bytes_in_use=peak_bytes())
        del state, metrics, step, bundle
        gc.collect()
    diff = abs(loss["hier"] - loss["naive"])
    say("train_2x2", hier_vs_naive_abs_diff=diff)
    check(math.isfinite(loss["hier"]) and diff <= 1e-4 * abs(loss["naive"]),
          f"2x2 hier loss {loss['hier']} != naive {loss['naive']}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, serve and collectives on one chip; "
                         "4: the multi-chip collectives and a 2x2 train "
                         "step only")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); this check runs only on a chip")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: asked for {args.chips} chips, found "
                 f"{len(devs)}")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    say("device", platform=devs[0].platform, device_kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, compile_cache=cache)

    t0 = time.perf_counter()
    if args.chips == 4:
        shapes = 0
        for vc in four_chip_clusters():
            collectives_on(vc)
            shapes += 1
        check(shapes >= 3, f"only {shapes} cluster shapes ran")
        train_step_2x2()
    else:
        phase_train()
        phase_serve()
        phase_collectives_one_chip()
    say("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
