"""What the benchmark takes from the program: its train-step state built
from the benchmark's weights, the jitted step, and a loader that feeds a
pool of batches to the program's prefetcher.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List

import jax


@dataclasses.dataclass
class Cell:
    """One cell's system under test, built and on the device."""
    state: Any                  # the program's train state (params, opt, ...)
    step: Callable              # the jitted, donated train step
    pool: List[Dict[str, Any]]  # host batches, fed in turn
    counts: Dict[str, int]      # work per step: samples, tokens
    reference: Any              # the family's reference module


class PoolLoader:
    """Step-indexed loader over a pool of host batches: batch k is
    pool[k % len(pool)], so the first len(pool) steps all differ."""

    def __init__(self, pool):
        self.pool = pool

    def batch(self, step: int):
        return self.pool[step % len(self.pool)]


def _leaves(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): (tuple(v.shape), str(v.dtype))
            for p, v in flat}


def check_layout(ours, program):
    """The benchmark's weight trees must be exactly the program's: same
    leaves, shapes and dtypes."""
    a, b = _leaves(ours), _leaves(program)
    if a != b:
        diff = sorted(set(a.items()) ^ set(b.items()))[:8]
        raise RuntimeError(f"benchmark weights do not match the program's "
                           f"layout: {diff}")


def check_optimizer(opt):
    """The step passes no b1, b2 or eps to `adamw_update`, so the
    configuration's must be its defaults: the reference uses the file's."""
    import inspect
    from repro.optim import adamw_update
    params = inspect.signature(adamw_update).parameters
    differ = {k: (opt[k], params[k].default) for k in ("b1", "b2", "eps")
              if opt[k] != params[k].default}
    if differ:
        raise RuntimeError(f"the configuration's AdamW settings differ from "
                           f"the program's (file, program): {differ}")


def make_state(init_weights, program_init, key, mesh):
    """The program's train state from `init_weights(key) -> (params,
    frozen)`, made on the device in one jitted call straight into the
    program's state shardings. `program_init(key)` is the program's own
    initialiser, traced for its layout only."""
    from repro.core import mpsl

    def init(key):
        params, frozen = init_weights(key)
        state = mpsl.init_state(params, frozen)
        state["rng"] = jax.random.fold_in(key, 3)
        return state

    shapes = jax.eval_shape(init, key)
    check_layout((shapes["params"], shapes["frozen"]),
                 jax.eval_shape(program_init, key))
    shardings = mpsl.state_shardings(shapes, mesh)
    return jax.jit(init, out_shardings=shardings)(key)
