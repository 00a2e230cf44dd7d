"""The benchmark's own copy of the synthetic porous-volume generator.

Copied from the program's ``repro.core.synthetic`` (binary volume only) so
that a later change to the program cannot move the yardstick: the slices a
cell segments are a function of ``--seed`` and of this file alone.

Source: arXiv 1809.05018 §4.1.1, a 512x512x512 porous volume corrupted with
salt-and-pepper noise, additive Gaussian noise and ringing.  The corruption
strengths are the configuration's (``corruption`` in its JSON file).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

VOID_LEVEL = 60.0
SOLID_LEVEL = 180.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 64 bits (both words count)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def porous_ground_truth(key, shape, porosity, correlation_length):
    """Binary (0 void, 1 solid) field: low-passed white noise thresholded at
    the porosity quantile."""
    h, w = shape
    noise = jax.random.normal(key, shape)
    fy = jnp.fft.fftfreq(h)[:, None]
    fx = jnp.fft.fftfreq(w)[None, :]
    lp = jnp.exp(-0.5 * ((fy**2 + fx**2) * (correlation_length**2) * (2 * jnp.pi) ** 2))
    field = jnp.fft.ifft2(jnp.fft.fft2(noise) * lp).real
    return (field > jnp.quantile(field, porosity)).astype(jnp.int32)


def corrupt(key, ground_truth, *, gaussian_sigma, salt_pepper_frac,
            ringing_amplitude, ringing_period):
    """Ringing, additive Gaussian noise and salt and pepper, clipped to
    [0, 255] (float32)."""
    k_g, k_sp, _ = jax.random.split(key, 3)
    base = jnp.where(ground_truth > 0, SOLID_LEVEL, VOID_LEVEL)
    h, w = base.shape
    yy = jnp.arange(h)[:, None] - h / 2.0
    xx = jnp.arange(w)[None, :] - w / 2.0
    r = jnp.sqrt(yy**2 + xx**2)
    img = base + ringing_amplitude * jnp.sin(2.0 * jnp.pi * r / ringing_period)
    img = img + gaussian_sigma * jax.random.normal(k_g, (h, w))
    u = jax.random.uniform(k_sp, (h, w))
    img = jnp.where(u < salt_pepper_frac / 2.0, 255.0, img)
    img = jnp.where((u >= salt_pepper_frac / 2.0) & (u < salt_pepper_frac), 0.0, img)
    return jnp.clip(img, 0.0, 255.0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("n", "shape", "corruption"))
def _slices(key, first, *, n, shape, corruption):
    cfg = dict(corruption)
    porosity = cfg.pop("porosity")
    corr_len = cfg.pop("correlation_length")

    def one(i):
        k_gt, k_img = jax.random.split(jax.random.fold_in(key, i))
        gt = porous_ground_truth(k_gt, shape, porosity, corr_len)
        return corrupt(k_img, gt, **cfg), gt

    return jax.lax.map(one, first + jnp.arange(n))


def make_slices(seed: int, n: int, shape, corruption: dict, first: int = 0):
    """Slices ``first .. first+n-1`` of the volume drawn from ``seed``, made on
    the device in one call: ``(images (n, H, W) f32, truth (n, H, W) i32)``.
    Slice ``i`` depends only on ``(seed, i)``."""
    return _slices(
        seed_key(seed), jnp.int32(first), n=n, shape=tuple(shape),
        corruption=tuple(sorted(corruption.items())),
    )
