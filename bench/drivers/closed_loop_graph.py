"""Closed-loop serving of a model that runs on a neighbour graph: as
``closed_loop``, ``clients`` callers each send their next force call as
soon as the previous answer is back, call k of a client being its seeded
start geometry plus seeded jitter (never integrated).  The serving pools
build each structure's graph on the host.  The operations counted are
those of each answered structure's real edges, and the reference is handed
graphs from its own float64 neighbour builder, on the geometry each answer
was evaluated on."""
import gc
import time

import numpy as np

from bench.harness import serve as S
from bench.harness import traffic as T
from bench.harness.cell import Context, device_info, make_params


def _graph(cell, pos, mask):
    m = cell.config["model"]
    return cell.family.neighbours(pos, mask, m["max_radius"],
                                  m["max_neighbors"])


def _edges(pos, model_cfg) -> int:
    """Real edges of a structure's graph: each atom's neighbours closer
    than the cutoff, at most k of them."""
    pos = np.asarray(pos, np.float64)
    d = np.sqrt(np.sum(np.square(pos[None] - pos[:, None]), -1))
    within = (d < model_cfg["max_radius"]).sum(1) - 1
    return int(np.minimum(within, model_cfg["max_neighbors"]).sum())


def run(cell, env):
    mix, model_cfg = cell.mix, cell.config["model"]
    starts = T.closed_loop_start(mix, cell.config["elements"], env.seed)

    def next_pos(client, call, pos0):
        return T.jitter(env.seed, client, call, pos0, mix["jitter"])

    model = cell.program.build(cell.config)
    params = make_params(cell, env.seed)
    eng = S.make_engine(model, params, mix["buckets"])
    eng.warmup()
    env.open_window()
    setup_s = time.perf_counter() - env.t_start
    served = S.closed_loop(eng, starts, next_pos, env.seconds)
    env.close_window()
    device = device_info(env.devices)
    end = served.t0 + served.seconds
    flops = sum(3 * cell.family.forward_flops(
        model_cfg, len(r.species), _edges(r.pos, model_cfg))
        for r in served.requests if S.Served._answered(r) and r._done_t <= end)
    env.log(f"[serve] {len(served.requests)} requests, "
            f"{served.completed_in_window()} answered in the "
            f"{served.seconds}s window, {served.failed()} failed; steps "
            f"{eng.metrics.counters['steps']}")
    metrics = eng.metrics
    del eng, model
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_sample(cell, params, served, env.seed)
    env.log(f"[reference] {len(ref)} requests in "
            f"{time.perf_counter() - t0:.1f}s")
    gaps = S.serve_gaps([(r.energy, e, r.forces, f) for r, e, f in ref])
    return Context(cell, setup_s, served.seconds, flops, len(env.devices),
                   attempted=len(served.requests), failed=served.failed(),
                   gaps=gaps, served=served, serve_metrics=metrics,
                   device=device)


def reference_sample(cell, params, served, seed, dtype="float32"):
    """The reference over a seed-drawn sample of the answered requests, the
    largest among them, each padded to its bucket as a slot is and given
    the reference's own graph.  -> list of (request, E_ref, F_ref)."""
    import jax
    import jax.numpy as jnp

    done = [r for r in served.requests if S.Served._answered(r)]
    if not done:
        return []
    k = min(len(done), int(cell.mix["reference_sample"]))
    rng = T.rng_for(seed, 5)
    biggest = max(range(len(done)), key=lambda i: len(done[i].species))
    rest = [i for i in range(len(done)) if i != biggest]
    pick = [biggest] + list(rng.choice(rest, size=k - 1, replace=False)) \
        if k > 1 else [biggest]
    buckets = sorted(b[0] for b in cell.mix["buckets"])
    model = cell.config["model"]
    ef = jax.jit(jax.vmap(lambda p, *a: cell.family.energy_forces(
        p, *a, model, dtype), in_axes=(None, 0, 0, 0, 0, 0)))
    chunk = int(cell.mix["reference_chunk"])
    by_size = {}
    for i in pick:
        r = done[i]
        by_size.setdefault(next(b for b in buckets if b >= len(r.species)),
                           []).append(r)
    out = []
    for size, reqs in sorted(by_size.items()):
        for c in range(0, len(reqs), chunk):
            part = reqs[c: c + chunk]
            sp = np.zeros((chunk, size), np.int32)
            pos = (1e3 * (1 + np.arange(size)))[None, :, None] * np.ones(
                (chunk, size, 3), np.float32)
            mask = np.zeros((chunk, size), np.float32)
            for j, r in enumerate(part):
                n = len(r.species)
                sp[j, :n], pos[j, :n], mask[j, :n] = r.species, r.pos, 1.0
            graphs = [_graph(cell, pos[j], mask[j]) for j in range(chunk)]
            nbr = np.stack([g[0] for g in graphs])
            nbr_mask = np.stack([g[1] for g in graphs])
            e, f = ef(params, jnp.asarray(sp), jnp.asarray(pos),
                      jnp.asarray(mask), jnp.asarray(nbr), jnp.asarray(nbr_mask))
            e, f = np.asarray(e), np.asarray(f)
            for j, r in enumerate(part):
                out.append((r, float(e[j]), f[j, :len(r.species)]))
    return out
