"""Public jit'd wrappers: shape padding, layout handling, backend dispatch.

On TPU these call the Pallas kernels; on CPU they dispatch to the jnp
reference (identical semantics) unless `force_pallas=True`, which runs the
kernel body in interpret mode — that is how the test suite validates the
kernels on this CPU-only container.

Block sizes default to `tune="auto"`: the shape-aware autotuner
(`kernels/autotune.py`) resolves them per (kernel, backend, dtype,
shape-bucket) — persistent-cache winners when a sweep has run, roofline
cost-model ranking otherwise.  Explicit `bm=`/`bn=`/`bk=` kwargs always
override the tuner; `tune="off"` restores the legacy hand-picked constants.
Resolution is pure Python over static shapes, so it is trace-safe (the
distmat shard_map bodies call these wrappers mid-trace).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import autotune as _tune
from . import gemm as _gemm
from . import tsgram as _tsgram
from . import randsketch as _randsketch
from . import bsr as _bsr
from . import fusedgrad as _fg
from . import flash_attention as _fa
from . import selective_scan as _ss
from . import ref as _ref

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: Array, axis: int, multiple: int) -> Array:
    rem = (-x.shape[axis]) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def gemm(a: Array, b: Array, *, bm: int | None = None, bn: int | None = None,
         bk: int | None = None, tune: str = "auto", out_dtype=None,
         force_pallas: bool = False) -> Array:
    """C = A @ B, arbitrary shapes (padded up to tiles internally)."""
    if not (_on_tpu() or force_pallas):
        return _ref.gemm_ref(a, b, out_dtype)
    m, k = a.shape
    _, n = b.shape
    cfg = _tune.resolve("gemm", {"m": m, "k": k, "n": n}, a.dtype,
                        {"bm": bm, "bn": bn, "bk": bk}, tune=tune)
    bm_, bn_, bk_ = (min(cfg["bm"], _rup(m, 8)), min(cfg["bn"], _rup(n, 128)),
                     min(cfg["bk"], _rup(k, 128)))
    ap = _pad_to(_pad_to(a, 0, bm_), 1, bk_)
    bp = _pad_to(_pad_to(b, 0, bk_), 1, bn_)
    out = _gemm.gemm(ap, bp, bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype,
                     interpret=not _on_tpu())
    return out[:m, :n]


def tsgram(a: Array, *, bm: int | None = None, bn: int | None = None,
           tune: str = "auto", out_dtype=None,
           force_pallas: bool = False) -> Array:
    """G = AᵀA for tall-skinny A (n padded to lanes internally).  Grams too
    wide for one VMEM-resident accumulator run in (bn × bn) output tiles."""
    if not (_on_tpu() or force_pallas):
        return _ref.tsgram_ref(a, out_dtype)
    m, n = a.shape
    cfg = _tune.resolve("tsgram", {"m": m, "n": n}, a.dtype,
                        {"bm": bm, "bn": bn}, tune=tune)
    bm_ = min(cfg["bm"], _rup(m, 8))
    bn_ = _tune._tsgram_bn(cfg, {"n": n})
    ap = _pad_to(_pad_to(a, 0, bm_), 1, 128)
    out = _tsgram.tsgram(ap, bm=bm_, bn=bn_, out_dtype=out_dtype,
                         interpret=not _on_tpu())
    return out[:n, :n]


def randsketch(a: Array, q: Array, *, bm: int | None = None,
               bn: int | None = None, tune: str = "auto", out_dtype=None,
               force_pallas: bool = False) -> Array:
    """B = AᵀQ for conforming tall-skinny A (m×n), Q (m×r) — the
    randomized-SVD projection.  Output is tiled in bn-wide strips so
    arbitrary n fits VMEM; n, r padded to tiles internally."""
    if not (_on_tpu() or force_pallas):
        return _ref.randsketch_ref(a, q, out_dtype)
    m, n = a.shape
    _, r = q.shape
    cfg = _tune.resolve("randsketch", {"m": m, "n": n, "r": r}, a.dtype,
                        {"bm": bm, "bn": bn}, tune=tune)
    bm_ = min(cfg["bm"], _rup(m, 8))
    bn_ = min(cfg["bn"], _rup(n, 128))
    ap = _pad_to(_pad_to(a, 0, bm_), 1, bn_)
    qp = _pad_to(_pad_to(q, 0, bm_), 1, 128)
    out = _randsketch.randsketch(ap, qp, bm=bm_, bn=bn_, out_dtype=out_dtype,
                                 interpret=not _on_tpu())
    return out[:n, :r]


def bsr_matmul(a: "_bsr.BlockELL", x: Array, *,
               force_pallas: bool = False) -> Array:
    """y = A @ X for block-sparse A.  Off-TPU dispatch goes to the
    structure-exploiting gather/einsum form (flops ∝ stored blocks), not the
    densifying oracle — the oracle stays in kernels/ref.py for tests."""
    if not (_on_tpu() or force_pallas):
        return _bsr.bsr_matmul_jnp(a, x)
    nx = x.shape[1]
    xp = _pad_to(x, 1, 128)
    out = _bsr.bsr_matmul(a, xp, interpret=not _on_tpu())
    return out[:, :nx]


def bsr_matvec(a: "_bsr.BlockELL", x: Array, *,
               force_pallas: bool = False) -> Array:
    """y = A @ x for block-sparse A and a vector x (n,)."""
    if not (_on_tpu() or force_pallas):
        return _bsr.bsr_matvec_jnp(a, x)
    if _bsr.spmv_vmem(a) > _tune.VMEM_BUDGET:
        # x too wide to stay VMEM-resident: gather its blocks per slot.
        return bsr_matmul(a, x[:, None], force_pallas=force_pallas)[:, 0]
    return _bsr.bsr_matvec(a, x, interpret=not _on_tpu())


def bsr_rmatmul(a: "_bsr.BlockELL", x: Array, *,
                force_pallas: bool = False) -> Array:
    """y = Aᵀ @ X for block-sparse A and dense X (m × nx)."""
    if not (_on_tpu() or force_pallas):
        return _bsr.bsr_rmatmul_jnp(a, x)
    nx = x.shape[1]
    xp = _pad_to(x, 1, 128)
    out = _bsr.bsr_rmatmul(a, xp, interpret=not _on_tpu())
    return out[:, :nx]


def fused_grad(a: Array, x: Array, target: Array, weights: Array, *,
               loss: str, param: float = 1.0, bm: int | None = None,
               tune: str = "auto",
               force_pallas: bool = False) -> tuple[Array, Array, Array]:
    """(f, g, z) = (Σᵢ wᵢ ℓ((Ax)ᵢ, tᵢ), Aᵀ(w ∘ ℓ'(Ax, t)), Ax) for a dense
    row shard, reading A from HBM exactly once (kernels/fusedgrad).
    ``loss`` ∈ {"quad", "logistic", "huber", "poisson"}; ``param`` is the
    loss's static scalar (the huber δ).  Returns f float32 scalar, g (n,)
    in x.dtype, z (m,) row-space in float32."""
    if loss not in _fg.LOSSES:
        raise ValueError(f"loss must be one of {_fg.LOSSES}, got {loss!r}")
    m, n = a.shape
    if not (_on_tpu() or force_pallas):
        f, g, z = _fg.fused_grad_jnp(a, x, target, weights, loss=loss,
                                     param=param)
        return f, g.astype(x.dtype), z
    cfg = _tune.resolve("fusedgrad", {"m": m, "n": n}, a.dtype, {"bm": bm},
                        tune=tune)
    bm_ = min(cfg["bm"], _rup(m, 128))
    ap = _pad_to(_pad_to(a, 0, bm_), 1, 128)
    xp = _pad_to(x[None, :], 1, 128)
    # Padding rows get weight 0, so they contribute nothing to f or g.
    tp = _pad_to(target[None, :], 1, bm_)
    wp = _pad_to(weights[None, :], 1, bm_)
    f, g, z = _fg.fused_grad(ap, xp, tp, wp, loss=loss, param=param,
                             bm=bm_, interpret=not _on_tpu())
    return f[0, 0], g[0, :n].astype(x.dtype), z[0, :m]


def fused_grad_bsr(a: "_bsr.BlockELL", x: Array, target: Array,
                   weights: Array, *, loss: str, param: float = 1.0,
                   force_pallas: bool = False) -> tuple[Array, Array, Array]:
    """Fused (f, g, z) for a BlockELL shard — every stored block read once.
    Off-TPU dispatch goes to the gather/einsum structured form (flops ∝
    stored blocks); x/target/weights already conform to the padded dims.
    When the fused kernel's resident working set (x + gradient accumulator,
    ∝ n) cannot fit VMEM, falls back to a two-pass composition of the
    VMEM-safe BSR kernels (SpMV, residual on host-side jnp, transpose-
    multiply) — one extra read of the stored blocks, but it always runs."""
    if loss not in _fg.LOSSES:
        raise ValueError(f"loss must be one of {_fg.LOSSES}, got {loss!r}")
    if not (_on_tpu() or force_pallas):
        f, g, z = _fg.fused_grad_bsr_jnp(a, x, target, weights, loss=loss,
                                         param=param)
        return f, g.astype(x.dtype), z
    # int8-quantized shards compose the scale-aware SpMV/rmatmul kernels
    # (two reads of the stored blocks — still half the bytes of one f32
    # read); exact-mode shards keep the single-read fused kernel.
    if a.scales is not None or _fg.fused_grad_bsr_vmem(
            a.bs, a.ell, a.shape[1], a.data.dtype) > _tune.VMEM_BUDGET:
        z = bsr_matvec(a, x, force_pallas=force_pallas)
        f, r = _fg.row_loss_grad(z, target, weights, loss, param)
        g = bsr_rmatmul(a, r.astype(x.dtype)[:, None],
                        force_pallas=force_pallas)[:, 0]
        return f, g.astype(x.dtype), z.astype(jnp.float32)
    f, g, z = _fg.fused_grad_bsr(a, x, target, weights, loss=loss,
                                 param=param, interpret=not _on_tpu())
    return f, g.astype(x.dtype), z


def fused_grad_multi(a: Array, x: Array, target: Array, weights: Array, *,
                     loss: str, param: float = 1.0, bm: int | None = None,
                     tune: str = "auto", force_pallas: bool = False
                     ) -> tuple[Array, Array, Array]:
    """Request-batched fused gradients for a dense row shard: k right-hand
    sides answered in ONE streaming pass over A.  x (k, n), target/weights
    (k, m) → f (k,) float32, g (k, n) in x.dtype, z (k, m) float32.
    Padding request slots carry zero weights, so they contribute nothing."""
    if loss not in _fg.LOSSES:
        raise ValueError(f"loss must be one of {_fg.LOSSES}, got {loss!r}")
    m, n = a.shape
    k = x.shape[0]
    if not (_on_tpu() or force_pallas):
        f, g, z = _fg.fused_grad_multi_jnp(a, x, target, weights, loss=loss,
                                           param=param)
        return f, g.astype(x.dtype), z
    cfg = _tune.resolve("fusedgrad", {"m": m, "n": n, "k": _rup(k, 8)},
                        a.dtype, {"bm": bm}, tune=tune)
    bm_ = min(cfg["bm"], _rup(m, 128))
    ap = _pad_to(_pad_to(a, 0, bm_), 1, 128)
    # Pad the request axis to the f32 sublane multiple (8) and the feature
    # axis to the lane multiple; padding rows AND padding request slots get
    # weight 0, so they contribute nothing to f or g.
    xp = _pad_to(_pad_to(x, 0, 8), 1, 128)
    tp = _pad_to(_pad_to(target, 0, 8), 1, bm_)
    wp = _pad_to(_pad_to(weights, 0, 8), 1, bm_)
    f, g, z = _fg.fused_grad_multi(ap, xp, tp, wp, loss=loss, param=param,
                                   bm=bm_, interpret=not _on_tpu())
    return (f.sum(axis=1)[:k], g[:k, :n].astype(x.dtype), z[:k, :m])


def fused_grad_bsr_multi(a: "_bsr.BlockELL", x: Array, target: Array,
                         weights: Array, *, loss: str, param: float = 1.0,
                         force_pallas: bool = False
                         ) -> tuple[Array, Array, Array]:
    """Request-batched fused (f, g, z) for a BlockELL shard — every stored
    block read once, serving all k requests.  x (k, n), target/weights
    (k, m) over the padded BlockELL dims → f (k,), g (k, n), z (k, m).
    Falls back to a two-pass composition of the VMEM-safe BSR kernels when
    the kp-scaled resident working set cannot fit VMEM."""
    if loss not in _fg.LOSSES:
        raise ValueError(f"loss must be one of {_fg.LOSSES}, got {loss!r}")
    k = x.shape[0]
    if not (_on_tpu() or force_pallas):
        f, g, z = _fg.fused_grad_bsr_multi_jnp(a, x, target, weights,
                                               loss=loss, param=param)
        return f, g.astype(x.dtype), z
    kp = _rup(k, 8)
    # Quantized shards route through the scale-aware two-pass composition,
    # like the single-RHS form above.
    if a.scales is not None \
            or _fg.fused_grad_bsr_multi_vmem(a, kp) > _tune.VMEM_BUDGET:
        z = bsr_matmul(a, x.T, force_pallas=force_pallas).T
        le, r = _fg.row_loss_elem(z, target, weights, loss, param)
        g = bsr_rmatmul(a, r.astype(x.dtype).T, force_pallas=force_pallas).T
        return le.sum(axis=1), g.astype(x.dtype), z.astype(jnp.float32)
    xp = _pad_to(x, 0, 8)
    tp = _pad_to(target, 0, 8)
    wp = _pad_to(weights, 0, 8)
    f, g, z = _fg.fused_grad_bsr_multi(a, xp, tp, wp, loss=loss, param=param,
                                       interpret=not _on_tpu())
    return f[:k], g[:k].astype(x.dtype), z[:k]


def bsr_block_size(m: int, n: int, nnz: int, *, nx: int = 128,
                   dtype=jnp.float32, tune: str = "auto") -> int:
    """Autotuned BSR block size for an (m × n) matrix with `nnz` nonzeros.

    Resolved through the same persistent-cache/roofline machinery as the
    dense kernels: the cost model prices lane/sublane padding of small
    blocks against the extra zero-fill large blocks suffer at low density.
    Pure Python over static shapes — safe to call at trace/format time.
    """
    cfg = _tune.resolve("bsr", {"m": m, "n": n, "nnz": nnz, "nx": nx},
                        dtype, {"bs": None}, tune=tune)
    return int(cfg["bs"])


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    scale: float | None = None, bq: int | None = None,
                    bk: int | None = None, tune: str = "auto",
                    force_pallas: bool = False) -> Array:
    """q: (B, Hq, S, D), k/v: (B, Hkv, S, D) with Hq a multiple of Hkv.
    Returns (B, Hq, S, D)."""
    B, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    if not (_on_tpu() or force_pallas):
        out = _ref.flash_attention_ref(
            q.reshape(B * hq, sq, d), k.reshape(B * hkv, sk, d),
            v.reshape(B * hkv, sk, d), scale=scale, causal=causal,
            q_heads_per_kv=group)
        return out.reshape(B, hq, sq, d)
    cfg = _tune.resolve(
        "flash_attention",
        {"sq": sq, "sk": sk, "d": d, "causal": int(causal)}, q.dtype,
        {"bq": bq, "bk": bk}, tune=tune)
    bq_ = min(cfg["bq"], _rup(sq, 8))
    bk_ = min(cfg["bk"], _rup(sk, 128))
    qp = _pad_to(q.reshape(B * hq, sq, d), 1, bq_)
    kp = _pad_to(k.reshape(B * hkv, sk, d), 1, bk_)
    vp = _pad_to(v.reshape(B * hkv, sk, d), 1, bk_)
    # Padded KV columns sit at causal positions > every real query row, so
    # with causal=True they are masked out automatically; for non-causal we
    # fall back to explicit slicing of K/V (pad only Q).
    if not causal and kp.shape[1] != sk:
        raise NotImplementedError("non-causal requires S_k % bk == 0")
    out = _fa.flash_attention(qp, kp, vp, scale=scale, causal=causal,
                              bq=bq_, bk=bk_, q_heads_per_kv=group,
                              interpret=not _on_tpu())
    return out[:, :sq].reshape(B, hq, sq, d)


def selective_scan(x, dt, A, B, C, D, *, q: int | None = None,
                   tune: str = "auto", force_pallas: bool = False):
    """Fused Mamba1 scan; pads S to q and d to 128 internally."""
    if not (_on_tpu() or force_pallas):
        return _ref.selective_scan_ref(x, dt, A, B, C, D)
    Bt, S, d = x.shape
    N = A.shape[1]
    cfg = _tune.resolve("selective_scan", {"s": S, "d": d, "n": N}, x.dtype,
                        {"q": q}, tune=tune)
    q_ = min(cfg["q"], _rup(S, 8))
    xp = _pad_to(_pad_to(x, 1, q_), 2, 128)
    dtp = _pad_to(_pad_to(dt, 1, q_), 2, 128)
    Bp = _pad_to(B, 1, q_)
    Cp = _pad_to(C, 1, q_)
    Ap = _pad_to(A, 0, 128)
    Dp = _pad_to(D, 0, 128)
    out = _ss.selective_scan(xp, dtp, Ap, Bp, Cp, Dp, q=q_,
                             bd=min(128, xp.shape[2]),
                             interpret=not _on_tpu())
    return out[:, :S, :d]


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
