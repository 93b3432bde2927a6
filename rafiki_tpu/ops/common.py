"""Shared dispatch policy for the Pallas ops.

One place decides when the kernels run vs the pure-XLA fallback so
attention and patch-embed can't drift apart.
"""

from __future__ import annotations

from typing import Optional

import jax


def use_xla_fallback(interpret: Optional[bool]) -> bool:
    """True → run the mathematically equivalent pure-XLA path.

    Policy: templates call ops with ``interpret=None``; off-TPU that means
    the XLA path (the Pallas interpreter is orders of magnitude slower on
    CPU and is exercised separately by the kernel-equivalence tests via
    ``interpret=True``). On TPU, ``None`` means real Mosaic lowering.
    """
    return interpret is None and jax.default_backend() != "tpu"


def shard_map_checked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes checker ON — for
    pure XLA bodies (no ``pallas_call``)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def shard_map_kernels(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` configured for bodies that may issue Pallas
    calls. The varying-manual-axes checker cannot type a ``pallas_call``'s
    outputs (jax requires an explicit ``vma`` on every out ShapeDtypeStruct
    it cannot infer), so kernel-bearing maps disable it; correctness of
    the replication/varying structure is covered by the oracle-equivalence
    tests instead."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def gqa_repeat_factor(n_heads: int, n_kv_heads: int) -> int:
    """Validate the GQA head pairing (q head i ↔ kv head ``i // rep``,
    the ``jnp.repeat`` convention shared by the sequence-parallel
    attention ops) and return ``rep = n_heads / n_kv_heads``."""
    if n_heads % n_kv_heads:
        raise ValueError(f"q heads {n_heads} must be a multiple of kv "
                         f"heads {n_kv_heads}")
    return n_heads // n_kv_heads
