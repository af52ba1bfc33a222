"""Train driver: the system's jitted train step on one chip.

Set-up builds one object, :class:`Program` — the step that
``repro.train.train_loop.make_train_step`` returns for ``Model(cfg)`` and
the run configuration, compiled ahead of time with its state donated —
makes the weights and a pool of token batches on the device from the
seed, and drives the step through its first ``check_steps`` steps on
batches that all differ.  The same object then runs the window: steps
back to back over the pool until ``--seconds`` have passed, the window
closing on the last step's state.

The check: once the window has closed and the program's state is freed,
the configuration's plain reference (float32, ``highest`` precision)
follows the same first steps from the same weights and batches.
:func:`gaps` gives the numbers; ``limits/<cell>.json`` names those
compared, each with its limit.  The first gradient is read from AdamW's
first moment, m₁ / (1 − β₁) — its leaf norms and its elements at fixed
positions (``reference.sample_indices``) — and the change from the
float32 master weights.  Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out
of the changes.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import flops as flopsmod
import generate
import harness
import reference as R

TRAIN_E2E = "train_tokens_per_s"


# ---------------------------------------------------------------------------
# the configuration and run configuration as stated in the cell's files
# ---------------------------------------------------------------------------

def _run_value(cfg, key: str):
    """What the system runs for a key of a configuration file's ``model``:
    the field of that name, a field of every pattern position as a list
    (``mlp``, ``sliding_window``; ``pattern`` is their ``kind``), or one of
    the derived widths below.  ``KeyError`` where it has none."""
    from repro.models.config import LayerSpec
    from repro.models.xlstm import N_SLSTM_BLOCKS
    derived = {"head_dim": lambda: cfg.resolved_head_dim,
               "pattern": lambda: [s.kind for s in cfg.pattern],
               "slstm_blocks": lambda: N_SLSTM_BLOCKS}
    if key in derived:
        return derived[key]()
    if key in {f.name for f in dataclasses.fields(LayerSpec)}:
        return [getattr(s, key) for s in cfg.pattern]
    if key in {f.name for f in dataclasses.fields(cfg)}:
        v = getattr(cfg, key)
        return list(v) if isinstance(v, tuple) else v
    raise KeyError(key)


def model_config(cell):
    """The system's configuration for the cell's file: ``arch`` with the
    file's ``overrides``, every key the file states under ``model``
    compared with what the system runs."""
    from repro.configs import get_config
    conf = cell.config
    cfg = get_config(conf["arch"]).scaled(**conf["overrides"])
    wrong, missing = {}, []
    for k, v in conf["model"].items():
        try:
            run = _run_value(cfg, k)
        except KeyError:
            missing.append(k)
            continue
        if run != v:
            wrong[k] = (v, run)
    if missing:
        raise ValueError(f"{conf['name']}: the system has no setting for "
                         f"the stated keys {missing}")
    if wrong:
        raise ValueError(f"{conf['name']}: the system's configuration "
                         f"differs from the file (stated, run): {wrong}")
    return cfg


def run_config(cell, full_cfg):
    """The knob space's default for the cell's shape and mesh, with the
    traffic's knobs and optimizer settings on top."""
    from repro.core import costmodel, knobs as knobmod
    from repro.models.config import SHAPES_BY_NAME
    from repro.runconfig import runconfig_from_knobs
    t = cell.traffic
    space, _, _ = knobmod.clean_space(full_cfg, SHAPES_BY_NAME[t["shape"]],
                                      getattr(costmodel, t["mesh"]))
    knobs = dict(space.project(space.default_config()))
    opt = {k: v for k, v in t["optimizer"].items()
           if k not in ("learning_rate", "eps")}
    knobs.update(t["knobs"])
    knobs.update(opt)
    rc = runconfig_from_knobs(knobs)
    for k, v in {**t["knobs"], **opt}.items():
        if getattr(rc, k) != v:
            raise ValueError(f"run config field {k}: stated {v!r}, the "
                             f"system set {getattr(rc, k)!r}")
    return rc


def pool_batches(cell, key, n: int):
    t = cell.traffic
    pool = generate.token_pool(
        jax.random.fold_in(key, 1), n=n, batch=t["batch"], seq=t["seq"],
        vocab=cell.config["model"]["vocab_size"], zipf=t["zipf"],
        doc_len=t["doc_len"])
    return [{"tokens": pool["tokens"][i], "labels": pool["labels"][i]}
            for i in range(n)]


def weights_key(key):
    return jax.random.fold_in(key, 0)


_samples = jax.jit(R.leaf_samples)


@jax.jit
def _change_norms(tree, init):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(w - w0))) for w, w0 in
                      zip(jax.tree.leaves(tree), jax.tree.leaves(init))])


def leaf_change_norms(tree, key, shapes):
    """Per-leaf ‖w − w₀‖ of float32 weights, w₀ drawn again from the seed
    beside ``tree``."""
    init = generate.init_params(key, shapes)
    out = np.asarray(jax.device_get(_change_norms(tree, init)), np.float64)
    del init
    return out


def initial_state(w, shapes, rc):
    """The train state from the float32 draws ``w``: they are the master
    weights, and the parameters are their rounding to the dtypes of the
    model's init.  (Masters rounded to bfloat16 first would all sit on its
    grid, and no update under half a bfloat16 step would move the
    parameters in the first steps, where the float32 reference moves at
    once.)"""
    from repro.train import optimizer as opt
    from repro.train.train_loop import TrainState
    params = jax.tree.map(lambda x, s: x.astype(s.dtype), w, shapes)
    state = opt.opt_init(params, rc)
    if rc.master_weights_f32:
        state = state._replace(master=w)
    return TrainState(params, state, jnp.zeros((), jnp.int32))


def flops_per_step(cell, cfg, shapes) -> float:
    """Model FLOPs of one step (:func:`flops.train_step`); the experts a
    token may use are counted against the published number where the
    file's ``reduced`` cuts ``n_experts``."""
    t = cell.traffic
    published = cell.config.get("reduced", {}).get("n_experts", {})
    return flopsmod.train_step(shapes, cfg, batch=t["batch"], seq=t["seq"],
                               experts_published=published.get("published"))


def kernel_calls(cell, cfg, rc) -> dict:
    """Operations and bytes of one call of each kernel the step runs, by
    tag (``flops/kernels/<tag>.py``)."""
    out = {}
    for tag, mod in flopsmod.kernel_modules().items():
        per_call = mod.call(cfg, rc, cell.traffic)
        if per_call is not None:
            out[tag] = per_call
    return out


def counts(cell, cfg, rc, shapes) -> dict:
    """The step's counts that the per-layer readers divide by, worked out
    at set-up: a layer kind with no module fails there."""
    return {"flops_per_step": flops_per_step(cell, cfg, shapes),
            "kernel_calls": kernel_calls(cell, cfg, rc)}


def trace_context(step_counts: dict, summary: dict, steps: int,
                  peak: dict) -> dict:
    """What ``metrics/<name>.py`` reads in a traced run: the trace's
    reduction (``trace.reduce``), the window's steps, the step's counts
    and the chip's peak."""
    return {**summary, "steps": steps, **step_counts, "peak": peak,
            "moves": TRAIN_E2E}


# ---------------------------------------------------------------------------
# faults planted under the timed path (tests and calibration only)
# ---------------------------------------------------------------------------

def _plant(step, fault: str, head_index: int, jitted=None):
    if fault is None:
        return step
    if fault == "unchanged":
        def unchanged(state, batch):
            _, mets = step(jax.tree.map(jnp.copy, state), batch)
            return state, mets
        return unchanged
    if fault == "half_batch":
        def half(state, batch):
            b = batch["tokens"].shape[0]
            if b > 1:
                batch = {k: v[: b // 2] for k, v in batch.items()}
            else:
                s = batch["tokens"].shape[1]
                batch = {k: v[:, : s // 2] for k, v in batch.items()}
            return jitted(state, batch)
        return half
    if fault == "altered":
        def altered(state, batch):
            new, mets = step(state, batch)
            opt = new.opt_state
            leaves, tdef = jax.tree.flatten(opt.master)
            leaves[head_index] = leaves[head_index] * 1.001
            return new._replace(opt_state=opt._replace(
                master=jax.tree.unflatten(tdef, leaves))), mets
        return altered
    raise ValueError(fault)


# ---------------------------------------------------------------------------
# the timed path
# ---------------------------------------------------------------------------

class Program:
    """The system's train step with its state, built from the seed."""

    def __init__(self, cell, seed: int, fault: str = None):
        from repro.configs import get_config
        from repro.models.model import Model
        from repro.train.train_loop import make_train_step

        t = cell.traffic
        self.cell = cell
        self.cfg = model_config(cell)
        self.rc = run_config(cell, get_config(cell.config["arch"]))
        model = Model(self.cfg)
        self.shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
        self.counts = counts(cell, self.cfg, self.rc, self.shapes)
        self.specs = generate.leaf_specs(self.shapes)
        self.key = jax.random.key(harness.seed_words(seed))
        rc, shapes, wkey = self.rc, self.shapes, weights_key(self.key)

        make_state = jax.jit(partial(initial_state, shapes=shapes, rc=rc),
                             donate_argnums=(0,))
        self.state = make_state(generate.init_params(wkey, shapes))
        self.batches = pool_batches(cell, self.key, t["pool"])
        lr = float(t["optimizer"]["learning_rate"])
        step = jax.jit(make_train_step(model, rc, lr_schedule=lambda s: lr),
                       donate_argnums=(0,))
        self.step = step.lower(self.state, self.batches[0]).compile()
        self.hlo = self.step.as_text()
        paths = [p for p, _, _ in self.specs]
        head = paths.index("head/w") if "head/w" in paths else 0
        self.run_step = _plant(self.step, fault, head, jitted=step)
        self.i = 0
        self.losses = []

    def check_steps(self) -> dict:
        """The first steps, read for the check."""
        b1 = float(self.cell.traffic["optimizer"]["beta1"])
        n = int(self.cell.traffic["check_steps"])
        idx = R.sample_indices(self.shapes)
        grad = sample = None
        for _ in range(n):
            self.state, mets = self.run_step(self.state, self.batches[self.i])
            self.i += 1
            self.losses.append(mets["loss"])
            if grad is None:
                m = self.state.opt_state.m
                grad = np.asarray(jax.device_get(R.leaf_norms(m)),
                                  np.float64) / (1.0 - b1)
                sample = [np.asarray(x, np.float64) / (1.0 - b1) for x in
                          jax.device_get(_samples(m, idx))]
        change = leaf_change_norms(self.state.opt_state.master,
                                   weights_key(self.key), self.shapes)
        return {"losses": [float(x) for x in self.losses], "grad": grad,
                "sample": sample, "change": change}

    def window(self, seconds: float) -> dict:
        """Steps back to back until ``seconds`` have passed; one step is
        kept in flight, and the window closes on the last step's state."""
        pool = len(self.batches)
        losses = []
        t_start = time.monotonic()
        deadline = t_start + seconds
        prev = None
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    self.state, mets = self.run_step(
                        self.state, self.batches[self.i % pool])
                self.i += 1
                losses.append(mets["loss"])
                if prev is not None:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        prev.block_until_ready()
                prev = mets["loss"]
                if time.monotonic() >= deadline:
                    break
            jax.block_until_ready(self.state)
        t_end = time.monotonic()
        vals = np.asarray(jax.device_get(losses), np.float64)
        return {"t_start": t_start, "window_s": t_end - t_start,
                "steps": len(losses),
                "failed": int(np.sum(~np.isfinite(vals)))}

    def free(self):
        del self.state, self.batches, self.losses
        self.run_step = self.step = None


# ---------------------------------------------------------------------------
# the reference and the comparison
# ---------------------------------------------------------------------------

def reference_readings(cell, seed: int, mode: str = "f32",
                       fault: str = None) -> dict:
    """The plain reference through the first steps, from the seed.
    ``mode="fp8"`` is the control; ``fault`` plants "half_batch" or
    "altered" in the reference put in the program's place."""
    from repro.models.model import Model
    t = cell.traffic
    ref = cell.reference()
    cfg = model_config(cell)
    shapes = jax.eval_shape(
        lambda: Model(cfg).init(jax.random.key(0)))
    specs = generate.leaf_specs(shapes)
    key = jax.random.key(harness.seed_words(seed))
    wkey = weights_key(key)
    n = int(t["check_steps"])
    batches = [(b["tokens"], b["labels"])
               for b in pool_batches(cell, key, t["pool"])[:n]]
    model = cell.config["model"]
    mm = R.Matmul(mode)
    mask = None
    if fault == "half_batch":
        bt, s = batches[0][0].shape
        if bt > 1:
            mask = (jnp.arange(bt) < bt // 2)[:, None] * jnp.ones((1, s))
        else:
            mask = (jnp.arange(s) < s // 2)[None, :] * jnp.ones((bt, 1))

    def loss_fn(p, tokens, labels):
        return ref.loss(p, tokens, labels, model, mm, mask)

    paths = [p for p, _, _ in specs]
    double = paths.index("head/w") if fault == "altered" and \
        "head/w" in paths else -1
    params = generate.init_params(wkey, shapes)
    with jax.default_matmul_precision("highest"):
        losses, g1, s1, params = R.train_readings(loss_fn, params, batches,
                                                  t["optimizer"],
                                                  double_leaf=double)
    change = leaf_change_norms(params, wkey, shapes)
    del params
    return {"losses": losses, "grad": np.asarray(g1, np.float64),
            "sample": s1, "change": change}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers the check can compare (``limits/<cell>.json`` names the
    ones it does):

    * ``loss_gap``: relative gap of the first step's loss;
    * ``loss3_gap``: the worst of the three steps';
    * ``grad_gap``: worst leaf's gap between the first gradient's norms;
    * ``grad_median_gap``: the median leaf's gap between them, each leaf
      against its own reference norm;
    * ``grad_dir_gap``: the median leaf's distance between the directions
      of the first gradient's sampled elements, ‖ĝ − r̂‖ of the unit
      vectors (a leaf whose sample is all zeros in the reference is left
      out): it reads the gradient's precision, which a norm hardly sees;
    * ``update_gap``: worst leaf's gap between the changes' norms;
    * ``update_median_gap``: the median leaf's gap between them, each
      leaf against its own reference norm.
    """
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    rel = np.abs(lp - lr_) / np.abs(lr_)
    gr, gp = ref["grad"], prog["grad"]
    med = float(np.median(gr))
    live = gr >= 1e-3 * med
    cr, cp = ref["change"][live], prog["change"][live]
    cmed = float(np.median(cr))
    dirs = []
    for a, b, keep in zip(prog["sample"], ref["sample"], live):
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        if keep and nb > 0:
            dirs.append(np.linalg.norm(a / na - b / nb) if na > 0 else 1.0)
    out = {"loss_gap": float(rel[0]), "loss3_gap": float(np.max(rel)),
           "grad_gap": float(np.max(np.abs(gp - gr) / np.maximum(gr, med))),
           "grad_median_gap": float(np.median(np.abs(gp - gr)[live] /
                                              gr[live])),
           "grad_dir_gap": float(np.median(dirs)),
           "update_gap": float(np.max(np.abs(cp - cr) /
                                      np.maximum(cr, cmed))),
           "update_median_gap": float(np.median(np.abs(cp - cr) / cr))}
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


# ---------------------------------------------------------------------------

def run(cell, *, seed: int, seconds: float, trace: bool, t0: float,
        devices, fault: str = None) -> dict:
    import trace as tracemod
    t = cell.traffic
    prog = Program(cell, seed, fault=fault)
    readings = prog.check_steps()
    tdir = None
    if trace:
        tdir = harness.SCRATCH / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    win = prog.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    device = harness.device_info(devices)
    setup_s = win["t_start"] - t0
    tokens = t["batch"] * t["seq"]
    ctx = None
    if trace:
        summary = tracemod.reduce(tdir, tracemod.kernel_ops(
            prog.hlo))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = trace_context(prog.counts, summary, win["steps"],
                            harness.peak(device["kind"]))
    prog.free()
    ref = reference_readings(cell, seed)
    checks, correct = harness.judge(gaps(readings, ref), cell.limits)
    return {"correct": correct and win["failed"] == 0,
            "attempted": win["steps"], "failed": win["failed"],
            "end_to_end": {TRAIN_E2E: tokens * win["steps"] / win["window_s"],
                           "setup_s": setup_s},
            "checks": checks, "device": device, "trace": ctx,
            "readings": {"program": readings, "reference": ref}}
