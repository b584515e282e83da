"""Training cells: the program's own train step, timed and checked.

Set-up builds one object, :class:`Program`: the production step
``repro.runtime.steps.make_train_step(..., mode="hier")`` jitted with its
state donated, as the program's training loop does.  It makes the weights
on the device from the seed in one jitted call, places a small pool of
token batches with the step's batch sharding, and drives the step through
its first three steps, which compile it and give the readings that
``correct`` compares.  The same object and state then enter the window.

The window cycles through the pool's :data:`POOL` batches and dispatches
steps until ``seconds`` have passed, waiting on a step's loss only once
:data:`IN_FLIGHT` newer steps are queued behind it, then blocks on the last
step.  Its losses are read after it has closed.

``correct`` compares the first three steps with the plain reference
(``benchmark/references/<name>.py``) run on the same weights and rows,
after the program's state has been freed.  A cell compares the numbers its
limits file (``benchmark/limits/<cell>.json``) gives a limit:

* ``loss_gap``: worst relative gap of a step's mean loss;
* ``gnorm_gap``: worst relative gap of a step's global gradient norm before
  the clip;
* ``grad_gap``: worst leaf gap of the first gradient as the optimizer got
  it (read from AdamW's first moment after one step);
* ``update_gap``: worst leaf gap of the parameters' change over three steps.

A leaf gap is ``|norm_program - norm_reference|`` over the reference's norm
of that leaf or of the median leaf, whichever is larger.  ``update_gap``
leaves out leaves whose first reference gradient is under a thousandth of
the median leaf's, which move by round-off alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import pathlib
import shutil
import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HERE = pathlib.Path(__file__).resolve().parents[1]
FIRST_STEPS = 3
POOL = 4        # distinct batches a run places and cycles through
IN_FLIGHT = 2   # steps queued behind the oldest unread one
ACTS = {"gelu_pytorch_tanh": "gelu", "silu": "swiglu"}


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    return _load(HERE / "references" / f"{config['reference']}.py")


tokens = _load(HERE / "tokens.py")


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        act=ACTS[config["hidden_act"]], norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"])


def n_chips(config: dict) -> int:
    return math.prod(config["mesh"].values())


def _key(words):
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


# ---------------------------------------------------------------------------
# Readings and their comparison
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Readings:
    """What a run of the first steps gives: per step the mean loss and the
    global gradient norm before the clip; per compared leaf the norm of the
    first clipped gradient and of the parameters' change after the steps."""
    loss: list
    gnorm: list
    grad: np.ndarray
    update: np.ndarray


def _leaf_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    if keep is None:
        keep = np.ones(ref.shape, bool)
    floor = max(float(np.median(ref[keep])), 1e-30)
    gaps = np.abs(prog - ref)[keep] / np.maximum(ref[keep], floor)
    return float(np.max(gaps))


def compare(prog: Readings, ref: Readings) -> dict:
    """The numbers ``correct`` holds against the cell's limits."""
    def rel(a, b):
        return max(abs(x - y) / abs(y) for x, y in zip(a, b))
    keep = ref.grad >= 1e-3 * np.median(ref.grad)
    return {
        "loss_gap": rel(prog.loss, ref.loss),
        "gnorm_gap": rel(prog.gnorm, ref.gnorm),
        "grad_gap": _leaf_gap(prog.grad, ref.grad),
        "update_gap": _leaf_gap(prog.update, ref.update, keep),
    }


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

class Program:
    """The program's train step over the cell's mesh, with its state made
    from a seed.  ``wrap_step`` (tests only) wraps the step body before it
    is jitted, to plant a fault underneath the harness."""

    def __init__(self, config: dict, traffic: dict, devices, *,
                 compute_dtype=jnp.float32, wrap_step=None):
        from repro.core.topology import MeshTopology
        from repro.runtime.steps import make_train_step
        from repro.substrate.compat import make_mesh

        self.config, self.traffic = config, traffic
        self.ref = reference_module(config)
        axes = config["mesh"]
        topo = MeshTopology(dict(axes),
                            slow_axes=("pod",) if "pod" in axes else ())
        self.devices = list(devices)[:n_chips(config)]
        self.mesh = make_mesh(tuple(axes.values()), tuple(axes),
                              devices=self.devices)
        opt = config["optimizer"]
        bundle = make_train_step(
            model_config(config), topo, self.mesh, mode=config["mode"],
            lr=opt["lr"], weight_decay=opt["weight_decay"], clip=opt["clip"],
            compute_dtype=compute_dtype)
        fn = bundle.fn if wrap_step is None else wrap_step(bundle.fn)
        self.step = jax.jit(fn, donate_argnums=(0,))

        def sharding(spec):
            return NamedSharding(self.mesh, spec)
        shardings = jax.tree.map(sharding, bundle.state_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        self.batch_sharding = sharding(bundle.batch_spec["tokens"])
        want = jax.eval_shape(bundle.model.init_params)
        have = jax.eval_shape(lambda: self.ref.pack(
            self.ref.init_params(config, jax.random.key(0))))
        if jax.tree.structure(want) != jax.tree.structure(have) or any(
                a.shape != b.shape for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(have))):
            raise ValueError(
                f"the reference {config['reference']!r} does not lay its "
                f"weights out as the program does: program {want}, "
                f"reference {have}")

        def init(words):
            params = self.ref.pack(self.ref.init_params(config, _key(words)))
            zeros = jax.tree.map(jnp.zeros_like, params)
            return {"params": params, "m": zeros,
                    "v": jax.tree.map(jnp.zeros_like, params),
                    "step": jnp.zeros((), jnp.int32)}

        self.init = jax.jit(init, out_shardings=shardings)

        def norms(tree):
            out = []
            for path in self.ref.LAYOUT:
                leaf = tree
                for k in path:
                    leaf = leaf[k]
                axes_ = tuple(range(1 if path[0] in self.ref.LAYERED else 0,
                                    leaf.ndim))
                sq = jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                             axis=axes_)
                out += list(sq) if path[0] in self.ref.LAYERED else [sq]
            return jnp.sqrt(jnp.stack(out))

        self.norms = jax.jit(norms)

        def change(params, words):
            start = self.ref.pack(self.ref.init_params(config, _key(words)))
            return norms(jax.tree.map(jnp.subtract, params, start))

        self.change = jax.jit(change)

    def batches(self, seed: int) -> list:
        vocab = self.config["vocab_size"]
        return [jax.device_put(b, self.batch_sharding)
                for b in tokens.pool(self.traffic, vocab, seed, POOL)]

    def first_steps(self, seed: int, pool: list):
        """State from the seed, driven through the first steps.  Returns
        the state as the window gets it and the :class:`Readings`."""
        words = tokens.seed_words(seed)
        state = self.init(words)
        loss, gnorm, grad = [], [], None
        for i in range(FIRST_STEPS):
            state, met = self.step(state, {"tokens": pool[i]})
            loss.append(float(met["loss"]))
            gnorm.append(float(met["gnorm"]))
            if i == 0:
                b1 = self.config["optimizer"]["b1"]
                grad = np.asarray(self.norms(state["m"])) / (1.0 - b1)
        update = np.asarray(self.change(state["params"], words))
        return state, Readings(loss, gnorm, grad, update)

    def window(self, state, pool: list, seconds: float, *, trace_dir=None,
               compiles=None):
        """Timed steps.  Returns the state and a dict with ``steps``,
        ``window_s``, ``losses`` and ``compiles`` (backend compilations
        counted during the window)."""
        losses, pending = [], deque()
        n0 = compiles.count if compiles else 0
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir))
        Ann = jax.profiler.TraceAnnotation
        i = FIRST_STEPS
        with Ann("bench.window"):
            t0 = time.perf_counter()
            while True:
                with Ann("bench.feed"):
                    batch = {"tokens": pool[i % len(pool)]}
                with Ann("bench.dispatch"):
                    state, met = self.step(state, batch)
                losses.append(met["loss"])
                pending.append(met["loss"])
                i += 1
                if len(pending) > IN_FLIGHT:
                    with Ann("bench.block"):
                        pending.popleft().block_until_ready()
                if time.perf_counter() - t0 >= seconds:
                    break
            with Ann("bench.block"):
                jax.block_until_ready((state, met))
            window_s = time.perf_counter() - t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        n_comp = (compiles.count - n0) if compiles else 0
        return state, {"steps": len(losses), "window_s": window_s,
                       "losses": [float(x) for x in losses],
                       "compiles": n_comp}


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------

def reference_readings(config: dict, traffic: dict, seed: int, devices, *,
                       rows=None, half_tokens: bool = False,
                       matmul: str = "float32") -> Readings:
    """The plain reference over the same weights and batches as the
    program's first steps.  Its state is spread over the cell's devices
    (each leaf split along its largest divisible axis, weights gathered
    whole for the forward pass, rows split over the devices), so that it
    fits where the program ran.  ``rows`` and ``half_tokens`` leave part
    of each batch out, and ``matmul="fp8"`` lowers the precision of the
    projections: the faults and the control a check has to catch."""
    ref = reference_module(config)
    devs = list(devices)[:n_chips(config)]
    mesh = Mesh(np.array(devs), ("r",))
    n = len(devs)

    def split(shape):
        dims = [i for i, s in enumerate(shape) if s % n == 0]
        if not dims:
            return NamedSharding(mesh, P())
        best = max(dims, key=lambda i: shape[i])
        spec = [None] * len(shape)
        spec[best] = "r"
        return NamedSharding(mesh, P(*spec))

    shapes = ref.shapes(config)
    pshard = {k: split(s) for k, s in shapes.items()}
    whole = NamedSharding(mesh, P())
    words = tokens.seed_words(seed)
    init = jax.jit(lambda w: ref.init_params(config, _key(w)),
                   out_shardings=pshard)

    def gather(p):
        return jax.lax.with_sharding_constraint(
            p, {k: whole for k in p})

    opt = config["optimizer"]

    def step(params, m, v, t, toks):
        return ref.train_step(config, opt, params, m, v, t, toks,
                              gather=gather, matmul=matmul)

    stepj = jax.jit(step, donate_argnums=(0, 1, 2),
                    out_shardings=(pshard, pshard, pshard, whole, whole,
                                   whole))
    params = init(words)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    loss, gnorm, grad = [], [], None
    for i in range(FIRST_STEPS):
        b = tokens.batch(traffic, config["vocab_size"], seed, i)
        if rows is not None:
            b = b[rows]
        if half_tokens:
            b = b[:, : b.shape[1] // 2 + 1]
        rows_split = b.shape[0] % n == 0
        b = jax.device_put(b, NamedSharding(mesh, P("r") if rows_split
                                            else P()))
        params, m, v, lval, gn, gsq = stepj(params, m, v, i + 1, b)
        loss.append(float(lval))
        gnorm.append(float(gn))
        if i == 0:
            grad = np.sqrt(np.asarray(gsq))
    del m, v
    start = init(words)
    delta = jax.jit(lambda a, b: ref.leaf_sq_norms(
        config, jax.tree.map(jnp.subtract, a, b)))(params, start)
    update = np.sqrt(np.asarray(delta))
    return Readings(loss, gnorm, grad, update)


# ---------------------------------------------------------------------------
# One run of a cell
# ---------------------------------------------------------------------------

def peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip: the larger of the allocator's
    peak reservation, which holds the compiled programs' temporaries, and
    its peak of buffers in use."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(max(int(s.get("peak_bytes_reserved", 0)),
                   int(s.get("peak_bytes_in_use", 0))) for s in stats)


def run(cell: dict, *, seed: int, seconds: float, trace_dir=None,
        devices, t_start: float, compiles=None, wrap_step=None) -> dict:
    """One run: set-up, window, correctness.  Returns the raw numbers the
    metric readers and the result line are made from."""
    config, traffic = cell["config"], cell["traffic"]
    prog = Program(config, traffic, devices, wrap_step=wrap_step)
    pool = prog.batches(seed)
    state, first = prog.first_steps(seed, pool)
    setup_s = time.monotonic() - t_start
    state, win = prog.window(state, pool, seconds, trace_dir=trace_dir,
                             compiles=compiles)
    used = prog.devices
    peak = peak_bytes(used)
    print(f"setup_s={setup_s:.3f} window_s={win['window_s']:.3f} "
          f"steps={win['steps']} memory_stats={used[0].memory_stats()}",
          file=sys.stderr)
    # the traced step's compiled module names the fusions that run
    # collectives; the compile is a hit in the persistent cache
    hlo_text = "" if trace_dir is None else prog.step.lower(
        state, {"tokens": pool[0]}).compile().as_text()
    del state, pool, prog
    t_ref = time.monotonic()
    ref = reference_readings(config, traffic, seed, used)
    print(f"reference_s={time.monotonic() - t_ref:.3f}", file=sys.stderr)
    numbers = compare(first, ref)
    limits = cell["limits"]
    for k in numbers.keys() - limits.keys():
        print(f"reading {k} = {numbers[k]!r} (not compared in this cell)",
              file=sys.stderr)
    numbers = {k: v for k, v in numbers.items() if k in limits}
    failed = sum(1 for x in win["losses"] if not math.isfinite(x))
    correct = failed == 0 and all(
        math.isfinite(v) and v <= limits[k] for k, v in numbers.items())
    tok_per_step = traffic["global_batch"] * traffic["seq_len"]
    return {
        "correct": correct,
        "attempted": win["steps"],
        "failed": failed,
        "checks": {k: {"value": v, "limit": limits[k]}
                   for k, v in numbers.items()},
        "setup_s": setup_s,
        "window_s": win["window_s"],
        "steps": win["steps"],
        "tokens": win["steps"] * tok_per_step,
        "compiles": win["compiles"],
        "memory_peak_bytes": peak,
        "hlo_text": hlo_text,
        "devices": used,
        "first": first,
        "reference": ref,
    }
