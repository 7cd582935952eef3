"""Open-loop serving: requests arrive on a seeded Poisson schedule at the
mix's fixed rate (``rate_per_s``), whether or not earlier ones are done;
latency runs from each request's due time."""
from bench.harness import serve as S
from bench.harness import traffic as T


def run(cell, env):
    arrivals = T.open_loop(cell.mix, cell.config["elements"], env.seed,
                           env.seconds)
    return S.run_serving(cell, env,
                         lambda eng: S.open_loop(eng, arrivals, env.seconds))
