"""Faults planted in the timed path, for the tests that see ``correct``
come out false: each replaces the compiled step with a broken one of
the same shapes."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def unchanged(inner, state, batch, t):
    """The step returns its state as it came."""
    _, aux = inner(state, batch, t)
    return state, aux


def half_batch(inner, state, batch, t):
    """Half of each worker's rows left out; the mean is over the rest."""
    half = jax.tree.map(lambda a: a[:, : a.shape[1] // 2], batch)
    return inner(state, half, t)


def no_exchange(inner, state, batch, t):
    """The server's estimate takes worker 0's message alone."""
    new, aux = inner(state, batch, t)
    new = dict(new)
    new["g_server"] = jax.tree.map(
        lambda gs, gw1, gw0: gs + (gw1[0] - gw0[0]),
        state["g_server"], new["g_w"], state["g_w"])
    return new, aux


def altered(inner, state, batch, t):
    """One entry of the produced parameters is off by 1."""
    new, aux = inner(state, batch, t)
    new = dict(new)
    leaves, tree = jax.tree.flatten(new["x"])
    i = max(range(len(leaves)), key=lambda j: leaves[j].ndim)
    a = leaves[i]
    leaves[i] = a.reshape(-1).at[0].add(jnp.asarray(1.0, a.dtype)) \
        .reshape(a.shape)
    new["x"] = jax.tree.unflatten(tree, leaves)
    return new, aux


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "altered": altered}


def plant(monkeypatch, fault):
    """Make ``Program.compile`` build ``fault`` around the program's
    step."""
    from chipbench import program

    def compile_broken(self, state, batch):
        inner = self.trainer.make_step()
        self.t = jnp.float32(self.traffic["radius"])
        step = jax.jit(lambda st, b, t: fault(inner, st, b, t),
                       donate_argnums=(0,))
        self.executable = step.lower(state, batch, self.t).compile()
        return self.executable

    monkeypatch.setattr(program.Program, "compile", compile_broken)
