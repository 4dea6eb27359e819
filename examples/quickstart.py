"""Quickstart: FLECS-CGD on a federated logistic-regression problem.

    PYTHONPATH=src python examples/quickstart.py

Runs the paper's Algorithm 1 (FedSONIA direction, direct Hessian update,
random-dithering compression) on a synthetic heterogeneous federation and
prints objective / gradient norm / communicated bits per node.  The whole
trajectory is one compiled lax.scan program (``repro.core.driver``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.driver import run_experiment
from repro.core.flecs import (FlecsConfig, hparams_from_config, init_state,
                              make_flecs_sweep_step)
from repro.data.logreg import make_problem
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    prob = make_problem(d=123, n_workers=20, r=64, mu=1e-3, seed=0)
    local_grad, local_hvp = prob.make_oracles()

    cfg = FlecsConfig(
        m=4,                          # sketch memory (columns of S_k)
        grad_compressor="dither64",   # the "CGD" part — set "identity" for FLECS
        hess_compressor="dither64",
        alpha=1.0, beta=1.0, gamma=1.0,
    )
    # the sweep step at the config's own hparams point: one run
    step = functools.partial(make_flecs_sweep_step(cfg, local_grad, local_hvp),
                             hparams_from_config(cfg))
    state = init_state(jnp.zeros(prob.d), prob.n_workers)

    iters = 201
    state, tr = run_experiment(step, state, jax.random.key(0), iters,
                               record=lambda st: prob.metrics(st.w))
    F = np.asarray(tr["F"])
    g = np.sqrt(np.asarray(tr["grad_sq"]))
    kbits = np.asarray(tr["bits_per_node"]).max(axis=1) / 1e3
    print(f"{'iter':>5s} {'F(w)':>10s} {'||grad||':>10s} {'kbits/node':>11s}")
    for k in range(0, iters, 25):
        print(f"{k:5d} {F[k]:10.6f} {g[k]:10.2e} {kbits[k]:11.1f}")
    print("done — compare against examples/federated_logreg.py for the "
          "FLECS/DIANA/FedNL baselines on the same problem.")


if __name__ == "__main__":
    main()
