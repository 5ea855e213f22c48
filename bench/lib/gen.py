"""Benchmark data made on the device, in float32, from a seed.

Device-side copies of the regimes of `repro.data.synthetic` (which builds
on the host in float64): the overlapping Gaussian mixture and the
heavy-tailed mixture of the paper's Table-1 stand-ins.  Each array is
made by one jitted call, so set-up pays no host generation or transfer.
Seeds are folded into 32-bit keys, so any whole number is a seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key(seed: int, stream: int = 0):
    """A PRNG key for ``seed`` (any whole number) and a sub-stream."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


@functools.partial(jax.jit, static_argnames=("d", "n_comp"))
def mixture_params(k, *, d: int, n_comp: int, spread: float):
    """Centres and per-component scales of the overlapping Gaussian
    mixture (`synthetic._gaussian_mixture`)."""
    kc, ks = jax.random.split(k)
    centers = jax.random.normal(kc, (n_comp, d), jnp.float32) * spread
    scales = jax.random.uniform(ks, (n_comp, 1), jnp.float32, 0.6, 1.8)
    return centers, scales


@functools.partial(jax.jit, static_argnames=("n",))
def mixture_rows(k, centers, scales, *, n: int):
    """``n`` fresh rows of the mixture."""
    kc, kz = jax.random.split(k)
    comp = jax.random.randint(kc, (n,), 0, centers.shape[0])
    z = jax.random.normal(kz, (n, centers.shape[1]), jnp.float32)
    return centers[comp] + z * scales[comp]


@functools.partial(jax.jit, static_argnames=("n", "d", "n_comp"))
def heavy_tail(k, *, n: int, d: int, n_comp: int, df: float,
               spread: float):
    """The heavy-tailed stand-in (`synthetic._heavy_tail`): mixture
    centres plus Student-t-like noise, then each column standardised as
    `synthetic.make_dataset` does."""
    kc, km, kz, kx = jax.random.split(k, 4)
    centers = jax.random.normal(kc, (n_comp, d), jnp.float32) * spread
    comp = jax.random.randint(km, (n,), 0, n_comp)
    chi = jax.random.chisquare(kx, df, (n, 1), jnp.float32) / df
    x = centers[comp] + jax.random.normal(kz, (n, d), jnp.float32) \
        * jax.lax.rsqrt(chi)
    mean = jnp.mean(x, axis=0)
    std = jnp.sqrt(jnp.mean(jnp.square(x - mean), axis=0))
    return (x - mean) / jnp.maximum(std, 1e-6)


def dataset(spec: dict, seed: int):
    """The (N, d) training rows a configuration's ``data`` block names."""
    kind = spec["kind"]
    if kind == "gaussian_mixture":
        centers, scales = mixture_params(
            key(seed, 0), d=spec["d"], n_comp=spec["n_comp"],
            spread=spec["spread"])
        return mixture_rows(key(seed, 1), centers, scales, n=spec["n"])
    if kind == "heavy_tail":
        return heavy_tail(key(seed, 0), n=spec["n"], d=spec["d"],
                          n_comp=spec["n_comp"], df=spec["df"],
                          spread=spec["spread"])
    raise ValueError(f"unknown data kind {kind!r}")


def fresh_rows(spec: dict, seed: int, n: int, stream: int):
    """``n`` more rows of a Gaussian-mixture configuration's distribution
    (same centres as `dataset` with ``spec["seed"]``), drawn from
    ``seed``: codebooks and queries."""
    if spec["kind"] != "gaussian_mixture":
        raise ValueError("fresh rows are drawn from a gaussian_mixture")
    centers, scales = mixture_params(
        key(spec["seed"], 0), d=spec["d"], n_comp=spec["n_comp"],
        spread=spec["spread"])
    return mixture_rows(key(seed, stream), centers, scales, n=n)
