"""jit'd public wrappers over the Pallas kernels.

Every op takes ``impl``: "pallas" (the TPU kernel) or "xla" (the
pure-jnp oracle — also the dry-run lowering path, since Pallas-TPU
cannot lower on the CPU backend).  ``interpret=True`` runs a Pallas
kernel body in Python, on any backend; callers that want it (the CPU
tests) ask for it.

``flash_attention`` carries a custom_vjp whose backward is the oracle's
VJP: training through the Pallas forward is exact; a dedicated Pallas
backward kernel is a further optimization, not a correctness need.

Model-zoo layouts (B,S,H,D) are converted to kernel layouts (B,H,S,D)
here so call sites stay clean.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_fwd
from .flash_decode import flash_decode as _flash_decode
from .mamba_scan import mamba_scan as _mamba_scan
from .moe_gmm import gmm as _gmm
from .rmsnorm import rmsnorm as _rmsnorm
from .slstm_cell import slstm_seq as _slstm_seq


# ---------------------------------------------------------------------------
# flash attention (B,S,H,D) public layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attn_core(q, k, v, causal, scale, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               interpret=interpret)


def _flash_attn_fwd_rule(q, k, v, causal, scale, interpret):
    out = _flash_attn_core(q, k, v, causal, scale, interpret)
    return out, (q, k, v)


def _flash_attn_bwd_rule(causal, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=causal,
                                             scale=scale), q, k, v)
    return vjp(g)


_flash_attn_core.defvjp(_flash_attn_fwd_rule, _flash_attn_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    impl: str = "pallas", interpret: bool = False):
    """q:(B,S,H,D) k/v:(B,T,Hkv,D) -> (B,S,H,Dv)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if impl == "xla":
        out = ref.attention_ref(qt, kt, vt, causal=causal, scale=scale)
    else:
        out = _flash_attn_core(qt, kt, vt, causal, scale, interpret)
    return jnp.swapaxes(out, 1, 2)


def flash_decode(q, k, v, kv_len, *, scale=None, impl: str = "pallas",
                 interpret: bool = False):
    """q:(B,1,H,D) k/v:(B,T,Hkv,D) kv_len:(B,) -> (B,1,H,Dv)."""
    qk = q[:, 0].swapaxes(1, 1)                        # (B,H,D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if impl == "xla":
        out = ref.decode_ref(qk, kt, vt, kv_len, scale=scale)
    else:
        out = _flash_decode(qk, kt, vt, kv_len, scale=scale,
                            interpret=interpret)
    return out[:, None]


def mamba_scan(xh, dt, a_log, bm, cm, *, chunk: int = 128,
               impl: str = "pallas", interpret: bool = False):
    """Chunked SSD; signature mirrors models.ssm.ssd_chunked."""
    if impl == "xla":
        return ref.ssd_ref(xh, dt, a_log, bm, cm)
    return _mamba_scan(xh, dt, a_log, bm, cm, chunk=chunk,
                       interpret=interpret)


def moe_gmm(x, w, *, impl: str = "pallas",
            interpret: bool = False):
    if impl == "xla":
        return ref.gmm_ref(x, w)
    return _gmm(x, w, interpret=interpret)


def fused_rmsnorm(x, scale, *, eps: float = 1e-5, impl: str = "pallas",
                  interpret: bool = False):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if impl == "xla":
        out = ref.rmsnorm_ref(x2, scale, eps)
    else:
        out = _rmsnorm(x2, scale, eps=eps, interpret=interpret)
    return out.reshape(shape)


def slstm_seq(xg, r, bias, *, impl: str = "pallas",
              interpret: bool = False):
    """Fused sLSTM over a sequence: xg:(B,S,4,H,Dh) -> h:(B,S,H,Dh)."""
    if impl == "xla":
        return ref.slstm_seq_ref(xg, r, bias)
    return _slstm_seq(xg, r, bias, interpret=interpret)
