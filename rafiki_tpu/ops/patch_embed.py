"""ViT patch embedding as a fused Pallas matmul kernel.

The patch-embed conv (stride = kernel = patch size) is exactly a reshape
into flattened patches followed by one dense projection. XLA's layout ops
do the reshape for free; the Pallas kernel fuses the (N_patches, P·P·C) ×
(P·P·C, D) projection with the bias add, tiled to the MXU (BASELINE.md
config #3 names this kernel). f32 accumulation, bf16-friendly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _matmul_bias_kernel(x_ref, w_ref, b_ref, o_ref, *, n_k_blocks: int,
                        block_k: int):
    from jax.experimental import pallas as pl

    acc = jnp.zeros(o_ref.shape, jnp.float32)

    def body(kb, acc):
        x_blk = x_ref[:, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        w_blk = w_ref[pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        return acc + jax.lax.dot_general(
            x_blk, w_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, n_k_blocks, body, acc)
    o_ref[:, :] = (acc + b_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def matmul_bias(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                block_m: int = 256, block_n: int = 256, block_k: int = 512,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Tiled ``x @ w + b`` on the MXU; pads every dim to block multiples.

    Off-TPU with ``interpret=None`` this routes to plain XLA ``x @ w + b``
    (the interpreter is test-only, forced via ``interpret=True``).
    """
    from jax.experimental import pallas as pl

    from rafiki_tpu.ops.common import use_xla_fallback

    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and b.shape == (n,), (x.shape, w.shape, b.shape)
    if use_xla_fallback(interpret):
        # f32 math like the kernel, cast back to the input dtype
        return (x.astype(jnp.float32) @ w.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(x.dtype)
    interpret = bool(interpret)

    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(128, n))
    block_k = min(block_k, max(128, k))
    pad_m, pad_n, pad_k = ((-m) % block_m, (-n) % block_n, (-k) % block_k)
    xp = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    wp = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    bp = jnp.pad(b, (0, pad_n)).reshape(1, -1)
    mp, kp, np_ = m + pad_m, k + pad_k, n + pad_n

    kernel = functools.partial(_matmul_bias_kernel,
                               n_k_blocks=kp // block_k, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
        name="patch_embed",  # what a profile calls the kernel
    )(xp, wp, bp)
    return out[:m, :n]


def extract_patches(images: jnp.ndarray, patch_size: int) -> jnp.ndarray:
    """(B, H, W, C) → (B, H/P · W/P, P·P·C) via pure layout ops."""
    b, h, w, c = images.shape
    p = patch_size
    assert h % p == 0 and w % p == 0, (images.shape, p)
    x = images.reshape(b, h // p, p, w // p, p, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, hp, wp, P, P, C)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def patch_embed(images: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                patch_size: int,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """ViT patch embedding: (B,H,W,C) → (B, N_patches, D).

    ``w``: (P·P·C, D), ``b``: (D,).
    """
    patches = extract_patches(images, patch_size)
    bsz, n, k = patches.shape
    out = matmul_bias(patches.reshape(bsz * n, k), w, b,
                      interpret=interpret)
    return out.reshape(bsz, n, -1)


def _pe_fwd(images, w, b, patch_size, interpret):
    return patch_embed(images, w, b, patch_size, interpret), (images, w)


def _pe_bwd(patch_size, interpret, residuals, g):
    images, w = residuals
    bsz, n, d = g.shape
    patches = extract_patches(images, patch_size)
    k = patches.shape[-1]
    g2 = g.reshape(bsz * n, d).astype(jnp.float32)
    p2 = patches.reshape(bsz * n, k).astype(jnp.float32)
    dw = (p2.T @ g2).astype(w.dtype)
    db = jnp.sum(g2, axis=0).astype(w.dtype)
    dp = (g2 @ w.astype(jnp.float32).T).astype(images.dtype)
    # invert extract_patches layout
    p = patch_size
    h = images.shape[1]
    wd = images.shape[2]
    c = images.shape[3]
    dimg = dp.reshape(bsz, h // p, wd // p, p, p, c)
    dimg = dimg.transpose(0, 1, 3, 2, 4, 5).reshape(bsz, h, wd, c)
    return dimg, dw, db


patch_embed.defvjp(_pe_fwd, _pe_bwd)
