"""Force-matched training: fresh batches of ``batch`` structures of
``atoms`` atoms with Lennard-Jones labels, stepped through the program's
compiled train step for the window."""
from bench.harness import train as TN


def run(cell, env):
    return TN.run_training(cell, env)
