"""Training traffic: Zipf-Markov token streams, made on the device.

A copy of the program's ``SyntheticLM`` generator, kept with the
benchmark so that a change to the program's generator cannot change the
yardstick. Tokens follow a per-worker affine Markov chain with
Zipf-distributed jumps:

    x_{t+1} = (a_j * x_t + b_j) mod V   with probability ``follow``,
    x_{t+1} ~ Zipf(V)                    otherwise,

so each worker sees its own bigram law (the paper's heterogeneous
workers) over a Zipf marginal. Every batch is a pure function of
(seed, step): the same seed gives the same tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one above 2**32."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              seed >> 31)


def _zipf(key: jax.Array, shape, vocab: int) -> jax.Array:
    """Approximate Zipf(1) draws via the inverse CDF of a log-uniform."""
    u = jax.random.uniform(key, shape, jnp.float32, 1e-6, 1.0)
    return jnp.clip(jnp.floor(jnp.exp(u * jnp.log(float(vocab)))) - 1,
                    0, vocab - 1).astype(jnp.int32)


def _tokens(key: jax.Array, law_key: jax.Array, n_workers: int,
            per_worker: int, seq: int, vocab: int, follow: float,
            max_multiplier: int) -> jax.Array:
    """[n_workers, per_worker, seq] tokens of one step."""
    k0, k1 = jax.random.split(law_key)
    a = 1 + 2 * jax.random.randint(k0, (n_workers, 1), 0, max_multiplier)
    b = jax.random.randint(k1, (n_workers, 1), 0, vocab)
    k2, k3, k4 = jax.random.split(key, 3)
    lead = (n_workers, per_worker)
    x0 = _zipf(k2, lead, vocab)
    z = _zipf(k3, lead + (seq,), vocab)
    keep = jax.random.bernoulli(k4, follow, lead + (seq,))

    def step(x, zf):
        z_t, f_t = zf
        x = jnp.where(f_t, (a * x + b) % vocab, z_t)
        return x, x

    _, toks = jax.lax.scan(
        step, x0, (jnp.moveaxis(z, -1, 0), jnp.moveaxis(keep, -1, 0)))
    return jnp.moveaxis(toks, 0, -1)


def make_batches(seed: int, n_batches: int, n_workers: int,
                 per_worker: int, seq: int, vocab: int, follow: float,
                 max_multiplier: int) -> list:
    """``n_batches`` steps of traffic in one jitted call on the device:
    a list of ``{"tokens", "labels"}``, each [n_workers, per_worker,
    seq] int32. Step i's rows differ from every other step's."""
    key = seed_key(seed)
    law_key, step_key = jax.random.split(key)

    @jax.jit
    def build(law_key, step_key):
        out = []
        for i in range(n_batches):
            toks = _tokens(jax.random.fold_in(step_key, i), law_key,
                           n_workers, per_worker, seq + 1, vocab, follow,
                           max_multiplier)
            out.append({"tokens": toks[..., :-1], "labels": toks[..., 1:]})
        return out

    return build(law_key, step_key)


def traffic_batches(seed: int, traffic: dict, seq: int,
                    vocab: int) -> list:
    """The batch pool of one run of a cell: the traffic file's sizes and
    generator parameters, the configuration's sequence and vocabulary."""
    per_worker, rem = divmod(traffic["tokens_per_worker_step"], seq)
    if rem or per_worker < 1:
        raise ValueError(
            f"tokens_per_worker_step {traffic['tokens_per_worker_step']} "
            f"is not a whole number of sequences of {seq}")
    gen = traffic["generator"]
    return make_batches(seed, traffic["n_batches"], traffic["n_workers"],
                        per_worker, seq, vocab, gen["follow"],
                        gen["max_multiplier"])
