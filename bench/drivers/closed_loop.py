"""Closed-loop serving: ``clients`` callers, each sending its next force
call as soon as the previous answer is back; call k of a client is its
seeded start geometry plus seeded jitter (never integrated)."""
from bench.harness import serve as S
from bench.harness import traffic as T


def run(cell, env):
    mix = cell.mix
    starts = T.closed_loop_start(mix, cell.config["elements"], env.seed)

    def next_pos(client, call, pos0):
        return T.jitter(env.seed, client, call, pos0, mix["jitter"])

    return S.run_serving(cell, env, lambda eng: S.closed_loop(
        eng, starts, next_pos, env.seconds))
