"""Flash decode on every platform: the kernel compiled by Mosaic where the
program is lowered for a TPU, the same kernel interpreted elsewhere.  The
choice is made per lowering (``lax.platform_dependent``), so a program
compiled for a described TPU from a CPU host holds the kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_decode.kernel import flash_decode as _kernel
from repro.kernels.flash_decode.ref import decode_ref


def flash_decode(q, k_cache, v_cache, layer, lengths, *,
                 block_kv: int = 512, kv_scale: Optional[float] = None):
    kw = dict(block_kv=block_kv, kv_scale=kv_scale)
    return jax.lax.platform_dependent(
        q, k_cache, v_cache, layer, lengths,
        tpu=functools.partial(_kernel, interpret=False, **kw),
        default=functools.partial(_kernel, interpret=True, **kw))


__all__ = ["flash_decode", "decode_ref"]
