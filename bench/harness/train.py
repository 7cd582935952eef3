"""Force-matched training through the step that `repro.train.train_loop`
runs (`make_train_step`, jitted with params and optimizer state donated),
and a plain AdamW reference that follows its first three steps.

One `Trainer` is built in set-up, driven through its first steps there
(they compile), and handed to the window as it is.  Host spans: ``feed``
around the batch upload, ``train_step`` around the dispatch, ``wait``
around the blocking read of the previous step's loss.  `run_training` is
the whole run of a training cell.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config import TrainConfig
from repro.train.loop import make_train_step

from .cell import Context, device_info, make_params, pairs_within
from .trace import WINDOW_SPAN
from .traffic import train_batches

__all__ = ["Trainer", "train_config", "reference_steps", "leaf_gaps",
           "train_gaps", "run_training", "READ_STEPS"]

# steps the reference follows: losses of steps 1-3, the first gradient,
# and the parameters' change over the three
READ_STEPS = 3


def train_config(mix: dict) -> TrainConfig:
    o = mix["optimizer"]
    return TrainConfig(lr=o["lr"], warmup_steps=o["warmup_steps"],
                       total_steps=o["total_steps"],
                       weight_decay=o["weight_decay"], b1=o["b1"], b2=o["b2"],
                       eps=o["eps"], grad_clip=o["grad_clip"])


class Trainer:
    """The compiled step with its state."""

    def __init__(self, loss_fn, params, tcfg: TrainConfig):
        self.tcfg = tcfg
        step, opt = make_train_step(lambda p, b: (loss_fn(p, b), {}), tcfg)
        self._step = jax.jit(step, donate_argnums=(0, 1))
        self.params = params
        self.opt_state = jax.jit(opt.init)(params)
        self.steps = 0

    def step(self, batch):
        """Dispatch one step on a host batch; -> the step's loss (device)."""
        with TraceAnnotation("feed"):
            dev = {k: jnp.asarray(v) for k, v in batch.items()}
        with TraceAnnotation("train_step"):
            self.params, self.opt_state, m = self._step(
                self.params, self.opt_state, dev)
        self.steps += 1
        return m["loss"]

    def first_gradient(self):
        """The first step's gradient as the optimizer got it (clipped),
        from AdamW's first moment after step 1: mu = (1 - b1) g."""
        return jax.tree.map(lambda m: np.asarray(m) / (1.0 - self.tcfg.b1),
                            self.opt_state["mu"])

    def run_window(self, batches, seconds: float, clock=time.perf_counter):
        """Step on fresh batches until ``seconds`` have passed, one step
        dispatched ahead of the blocking read.  -> (steps completed,
        seconds from the window's open to the last completion)."""
        with TraceAnnotation(WINDOW_SPAN):
            return self._window(batches, seconds, clock)

    def _window(self, batches, seconds, clock):
        t0 = clock()
        done, prev, k = 0, None, 0
        while True:
            if k >= len(batches):
                raise RuntimeError(f"window outran its {len(batches)} "
                                   "prepared batches")
            cur = self.step(batches[k])
            k += 1
            if prev is not None:
                with TraceAnnotation("wait"):
                    prev.block_until_ready()
                done += 1
                if clock() - t0 >= seconds:
                    break
            prev = cur
        with TraceAnnotation("wait"):
            cur.block_until_ready()
        done += 1
        return done, clock() - t0


def reference_steps(loss_fn, params, batches, tcfg: TrainConfig,
                    steps: int = READ_STEPS):
    """AdamW with decoupled weight decay on >=2-D leaves, global-norm
    clipping and a warmup-cosine schedule, written out plainly.
    -> (losses, first clipped gradient, parameters after ``steps``)."""
    b1, b2, eps, wd = tcfg.b1, tcfg.b2, tcfg.eps, tcfg.weight_decay

    def lr(t):
        if t < tcfg.warmup_steps:
            return tcfg.lr * t / max(tcfg.warmup_steps, 1)
        u = min(max((t - tcfg.warmup_steps)
                    / max(tcfg.total_steps - tcfg.warmup_steps, 1), 0.0), 1.0)
        return tcfg.lr * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * u)))

    vg = jax.jit(jax.value_and_grad(loss_fn))
    p = params
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for t in range(1, steps + 1):
        loss, g = vg(p, {k: jnp.asarray(v) for k, v in batches[t - 1].items()})
        gn = float(jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                                for x in jax.tree.leaves(g))))
        scale = min(1.0, tcfg.grad_clip / max(gn, 1e-9))
        g = jax.tree.map(lambda x: x.astype(jnp.float32) * scale, g)
        if g1 is None:
            g1 = jax.tree.map(np.asarray, g)
        losses.append(float(loss))
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2, a = 1 - b1 ** t, 1 - b2 ** t, lr(t)
        p = jax.tree.map(
            lambda w, m, v: w - a * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                     + (wd * w if w.ndim >= 2 else 0.0)),
            p, mu, nu)
    return losses, g1, p


def leaf_gaps(prog, ref, keep=None):
    """Per leaf, the gap between the program's norm and the reference's,
    over the larger of the reference leaf's norm and the median leaf
    norm; -> (worst gap, its leaf index, median gap over the leaves).
    ``keep``: leaf indices to compare (default all)."""
    pn = np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                     for x in jax.tree.leaves(prog)])
    rn = np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                     for x in jax.tree.leaves(ref)])
    med = float(np.median(rn))
    idx = range(len(rn)) if keep is None else keep
    gaps = {i: abs(pn[i] - rn[i]) / max(rn[i], med, 1e-30) for i in idx}
    worst = max(gaps, key=gaps.get)
    return (float(gaps[worst]), int(worst),
            float(np.median(list(gaps.values()))))


def run_training(cell, env) -> Context:
    """Set-up builds one `Trainer` and drives it through the first
    ``READ_STEPS`` steps (they compile); the window steps that same
    trainer on fresh batches; then the reference follows the first steps.
    -> the run's `Context`."""
    mix, model_cfg = cell.mix, cell.config["model"]
    model = cell.program.build(cell.config)
    params = make_params(cell, env.seed)
    p0 = jax.tree.map(jnp.copy, params)
    tcfg = train_config(mix)
    n_batches = READ_STEPS + int(np.ceil(mix["max_steps_per_s"] * env.seconds))
    batches = train_batches(mix, cell.config["elements"], env.seed, n_batches)
    trainer = Trainer(model.loss, params, tcfg)
    losses = [float(trainer.step(batches[0]))]
    g1 = trainer.first_gradient()
    for b in batches[1:READ_STEPS]:
        losses.append(float(trainer.step(b)))
    p3 = jax.tree.map(np.asarray, trainer.params)
    env.open_window()
    setup_s = time.perf_counter() - env.t_start
    steps, elapsed = trainer.run_window(batches[READ_STEPS:], env.seconds)
    env.close_window()
    device = device_info(env.devices)
    used = batches[READ_STEPS: READ_STEPS + steps]
    flops = sum(9 * cell.family.forward_flops(
        model_cfg, len(sp), pairs_within(pos, model_cfg["cutoff"]))
        for b in used for sp, pos in zip(b["species"], b["pos"]))
    env.log(f"[train] {steps} steps in {elapsed:.3f}s; losses of steps "
            f"1-{READ_STEPS} {losses}")
    del trainer, model
    gc.collect()
    t0 = time.perf_counter()
    r_losses, r_g1, r_p3 = reference_steps(
        lambda p, b: cell.family.loss(p, b, model_cfg), p0, batches, tcfg)
    env.log(f"[reference] {READ_STEPS} steps in "
            f"{time.perf_counter() - t0:.1f}s; losses {r_losses}")
    return Context(cell, setup_s, elapsed, flops, len(env.devices),
                   attempted=steps, failed=0,
                   gaps=train_gaps(losses, g1, p3, p0, r_losses, r_g1, r_p3),
                   train_steps=steps, train_structs=steps * mix["batch"],
                   device=device)


def train_gaps(losses, g1, p3, p0, r_losses, r_g1, r_p3) -> dict:
    """The numbers compared for a training cell (see PERF.md): the worst
    relative loss gap of the first steps; the gap of norms of the first
    gradient by the median leaf (``grad_gap_median``: by the worst leaf it
    is set by the round-off of one small leaf, the many-body weights
    ``mb_w``, and swings from seed to seed); and by the worst leaf the gap
    of norms of the parameters' change (leaves whose reference gradient is
    under a thousandth of the median leaf's left out)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, r_losses))
    grad_gap_median = leaf_gaps(g1, r_g1)[2]
    rn = np.asarray([np.linalg.norm(np.asarray(x, np.float64))
                     for x in jax.tree.leaves(r_g1)])
    keep = [i for i, v in enumerate(rn) if v >= 1e-3 * np.median(rn)]

    def sub(a, b):
        return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                            - np.asarray(y, np.float64), a, b)

    change_gap = leaf_gaps(sub(p3, p0), sub(r_p3, p0), keep)[0]
    return {"loss_gap": loss_gap, "grad_gap_median": grad_gap_median,
            "change_gap": change_gap}
