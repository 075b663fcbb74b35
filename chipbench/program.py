"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program passes through here:
the model and ``Trainer`` built from a configuration's ``arch`` block
and a traffic file, the optimizer state made from the benchmark's own
weights, the compiled step, and per-leaf norms of that state. Nothing
here computes a result the benchmark compares.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp


def import_program(root: Path) -> None:
    """Put the checkout's ``src/`` on the path (the program is not
    installed)."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


class Program:
    """One cell's compiled training step and its state."""

    def __init__(self, root: Path, config: dict, traffic: dict):
        import_program(root)
        from repro.configs.base import ArchConfig
        from repro.models.api import build_model
        from repro.train.trainer import Trainer, TrainerConfig

        arch = config["arch"]
        self.arch_cfg = ArchConfig(name=config["name"], **arch)
        self.model = build_model(self.arch_cfg)
        self.traffic = traffic
        self.tcfg = TrainerConfig(
            n_workers=traffic["n_workers"], beta=traffic["beta"],
            w2s=traffic["w2s"], s2w=traffic["s2w"],
            radius=traffic["radius"], remat=True, use_pallas="auto",
            donate=True, trace_spans=True)
        self.trainer = Trainer(self.model, self.tcfg)
        self.t = None
        self.executable = None

    def param_layout(self) -> dict:
        """path -> (shape, dtype name) of the program's parameters."""
        from repro.models.api import abstract_params

        shapes, _ = abstract_params(self.model)
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            key = "/".join(str(getattr(k, "key", k)) for k in path)
            out[key] = (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
        return out

    def init_state(self, params: dict, key: jax.Array):
        """The optimizer state around the benchmark's weights."""
        opt, metas = self.trainer.opt, self.trainer.metas
        return jax.jit(lambda k, p: opt.init(k, p, metas))(key, params)

    def compile(self, state, batch):
        """The step, compiled ahead of time for these shapes."""
        bstruct = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch)
        self.t = jnp.float32(self.traffic["radius"])
        self.executable = self.trainer.jit_step(bstruct).lower(
            state, batch, self.t).compile()
        return self.executable

    def step(self, state, batch):
        return self.executable(state, batch, self.t)

    def footprint(self) -> dict:
        """XLA's account of the compiled step's device memory: the bytes
        of its arguments (state and batch), outputs, aliased outputs,
        temporaries (activations, gradients, scratch) and code, and
        ``peak``, the most that is live at once by the buffers'
        lifetimes. On the TPU the parts can add up to more than the peak,
        and more than the chip holds, as buffers that are never live
        together share space; the CPU's peak leaves the temporaries
        out."""
        m = self.executable.memory_analysis()
        parts = {k: int(getattr(m, f"{k}_size_in_bytes"))
                 for k in ("argument", "output", "alias", "temp",
                           "generated_code")}
        parts["peak"] = int(m.peak_memory_in_bytes)
        return parts


def flat_paths(tree) -> dict:
    """{"a/b/c": leaf} of a nested dict."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return out


def _norms(tree, lead: int) -> dict:
    """Per-leaf Frobenius norms over all but ``lead`` leading axes."""
    return {p: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                axis=tuple(range(lead, a.ndim))))
            for p, a in flat_paths(tree).items()}


@jax.jit
def _first_readout(state):
    return {"m_w": _norms(state["m_w"], 1), "g_w": _norms(state["g_w"], 1),
            "g_server": _norms(state["g_server"], 0),
            "agg": agg_gap(state)}


def agg_gap(state):
    """|G - mean_j G_j| / |G| over the whole state."""
    gs = jax.tree.leaves(state["g_server"])
    gw = jax.tree.leaves(state["g_w"])
    num = sum(jnp.sum(jnp.square(s - jnp.mean(w, axis=0)))
              for s, w in zip(gs, gw))
    den = sum(jnp.sum(jnp.square(s)) for s in gs)
    return jnp.sqrt(num / den)


def first_readout(state, beta: float) -> dict:
    """After step 1: per-worker gradient norms (M_j / beta, as M_j
    starts at 0), the workers' estimates, the server's estimate."""
    r = jax.device_get(_first_readout(state))
    return {"grad": {p: [float(x) / beta for x in v]
                     for p, v in r["m_w"].items()},
            "g_w": {p: [float(x) for x in v] for p, v in r["g_w"].items()},
            "g_server": {p: float(v) for p, v in r["g_server"].items()},
            "agg": float(r["agg"])}


@jax.jit
def _dx(x, x0):
    return {p: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - x0[p].astype(jnp.float32))))
        for p, a in flat_paths(x).items()}


def last_readout(state, params0) -> dict:
    """After the last checked step: |X - X0| per leaf and the aggregate
    gap."""
    dx = jax.device_get(_dx(state["x"], flat_paths(params0)))
    return {"dx": {p: float(v) for p, v in dx.items()},
            "agg": float(_agg_gap(state))}


_agg_gap = jax.jit(agg_gap)
