"""Jit'd dispatch wrappers for all kernels.

Every op takes ``implementation``: 'pallas' (the compiled TPU kernel),
'interpret' (Pallas interpreter —
correctness path used by tests), or 'xla' (pure-jnp reference semantics,
used by the dry-run so cost_analysis sees XLA-native HLO).

Block shapes left unspecified are resolved through a three-level fallback
(DESIGN.md §8.4): the measured tuning database (``tuner/db.py``) for this
exact (op, shape, dtype, backend); then app-level defaults installed by
:func:`configure` at launch startup (serve/train); then the safe built-in
constants.  Explicit keyword arguments always win — tests and benchmarks
that pin block shapes are unaffected.
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp

from . import conv2d as _conv2d
from . import dotprod as _dotprod
from . import flash_attention as _flash
from . import gemm as _gemm
from . import gemv as _gemv
from . import mamba2 as _mamba2
from . import ref
from . import rwkv6 as _rwkv6

IMPLEMENTATIONS = ("pallas", "interpret", "xla")

# safe built-in block shapes — the last-resort tier of resolve_blocks
DEFAULT_BLOCKS: dict[str, dict[str, int]] = {
    "gemm": {"bm": 256, "bn": 256, "bk": 512},
    "gemv": {"bm": 512, "bk": 512},
    "dot": {"bk": 2048},
    "conv2d": {"bk": 128},
}

# app-level defaults installed by configure(); shape-exact DB hits override
_APP_BLOCKS: dict[str, dict[str, int]] = {}
# lazy tuning-db handle: (path, mtime) -> TuningDB, reloaded when the
# artifact changes on disk (tuning runs merge-save into it)
_DB_STATE: dict = {"path": None, "mtime": None, "db": None}


def _mode(implementation: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if implementation == "pallas":
        return True, False
    if implementation == "interpret":
        return True, True
    if implementation == "xla":
        return False, False
    raise ValueError(f"implementation must be one of {IMPLEMENTATIONS}")


def set_tuning_db(path) -> None:
    """Point the dispatch layer at a tuning database artifact."""
    _DB_STATE.update(path=path, mtime=None, db=None)


def _tuning_db():
    """The current TuningDB, reloaded on mtime change; never raises."""
    from repro.tuner.db import DEFAULT_DB_PATH, TuningDB

    path = _DB_STATE["path"] or DEFAULT_DB_PATH
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return None
    if _DB_STATE["db"] is None or _DB_STATE["mtime"] != mtime \
            or _DB_STATE["path"] != path:
        try:
            _DB_STATE.update(path=path, mtime=mtime, db=TuningDB.load(path))
        except Exception as e:   # a broken artifact must not break dispatch
            warnings.warn(f"tuning db {path}: {e}; using defaults")
            _DB_STATE.update(path=path, mtime=mtime, db=None)
    return _DB_STATE["db"]


def _db_best(op: str, shape, dtype, implementation: str) -> dict[str, int]:
    """Shape-exact tuned blocks measured on this backend, filtered to the
    op's known block names so a malformed artifact can only narrow, never
    break, dispatch.  Blocks timed on another backend never stand in: the
    interpreter accepts tiles the chip's compiler refuses."""
    db = _tuning_db()
    if db is None:
        return {}
    dt = str(jnp.dtype(dtype))
    rec = db.best_config(op, shape, dt, implementation) or {}
    return {k: v for k, v in rec.items() if k in DEFAULT_BLOCKS[op]}


def resolve_blocks(op: str, shape, dtype, implementation: str,
                   **explicit) -> dict[str, int]:
    """Block shapes for one kernel call: built-in defaults, overridden by
    app-level tuned defaults, overridden by a shape-exact tuning-db record,
    overridden by explicit (non-None) caller arguments."""
    out = dict(DEFAULT_BLOCKS[op])
    out.update(_APP_BLOCKS.get(op, {}))
    if any(v is None for v in explicit.values()):
        out.update(_db_best(op, shape, dtype, implementation))
    out.update({k: v for k, v in explicit.items() if v is not None})
    return out


def configure(app: str = "default", db_path=None,
              solutions_path=None) -> dict[str, dict[str, int]]:
    """Install app-level tuned block shapes as process-wide dispatch
    defaults (called by launch/serve.py and launch/train.py at startup).

    Sources, in priority order: the tuning database's ``apps`` section (the
    accelerator the measured co-design committed for ``app``), then the
    solution registry (``core/solution.py``).  Returns what was installed
    ({} when nothing is tuned — dispatch stays on safe defaults).
    """
    from repro.core.solution import mxu_legal

    if db_path is not None:
        set_tuning_db(db_path)
    hw_dict = None
    db = _tuning_db()
    if db is not None:
        entry = db.apps.get(app)
        # apps entries are absorbed unvalidated: a malformed one must not
        # take down a launch — fall through to the registry instead
        if isinstance(entry, dict) and isinstance(entry.get("hw"), dict):
            hw_dict = entry["hw"]
    if hw_dict is None:
        try:
            from repro.core.solution import load_hw

            hw = (load_hw(app, solutions_path) if solutions_path is not None
                  else load_hw(app))
            if hw is not None:
                hw_dict = {"pe_rows": hw.pe_rows, "pe_cols": hw.pe_cols,
                           "pe_depth": hw.pe_depth}
        except Exception as e:
            warnings.warn(f"solution registry unavailable ({e}); "
                          f"dispatch stays on defaults")
    if hw_dict is None:
        return {}

    def dim(knob: str, default: int) -> int:
        v = hw_dict.get(knob, default)
        return int(v) if isinstance(v, (int, float)) else default

    installed = {
        "gemm": {"bm": mxu_legal(dim("pe_rows", 256), 8),
                 "bn": mxu_legal(dim("pe_cols", 256), 128),
                 "bk": mxu_legal(dim("pe_depth", 512), 128)},
        "gemv": {"bm": mxu_legal(dim("pe_rows", 512), 8),
                 "bk": mxu_legal(dim("pe_depth", 512), 128)},
        "dot": {"bk": mxu_legal(dim("pe_depth", 2048), 128)},
        "conv2d": {"bk": mxu_legal(dim("pe_cols", 128), 8)},
    }
    _APP_BLOCKS.update(installed)
    return installed


def reset_dispatch() -> None:
    """Forget configure()/set_tuning_db state (tests)."""
    _APP_BLOCKS.clear()
    _DB_STATE.update(path=None, mtime=None, db=None)


def matmul(a, b, *, bm: int | None = None, bn: int | None = None,
           bk: int | None = None, implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        return ref.gemm_ref(a, b)
    blk = resolve_blocks("gemm", (a.shape[0], b.shape[1], a.shape[1]),
                         a.dtype, implementation, bm=bm, bn=bn, bk=bk)
    return _gemm.gemm(a, b, interpret=interp, **blk)


def matvec(a, x, *, bm: int | None = None, bk: int | None = None,
           implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        return ref.gemv_ref(a, x)
    blk = resolve_blocks("gemv", a.shape, a.dtype, implementation,
                         bm=bm, bk=bk)
    return _gemv.gemv(a, x, interpret=interp, **blk)


def dot(a, b, *, bk: int | None = None, implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        return ref.dot_ref(a, b)
    blk = resolve_blocks("dot", a.shape, a.dtype, implementation, bk=bk)
    return _dotprod.dot(a, b, interpret=interp, **blk)


def conv2d(a, w, *, bk: int | None = None, implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        return ref.conv2d_ref(a, w)
    c, h, wd = a.shape
    k, _, r, s = w.shape
    blk = resolve_blocks("conv2d", (k, c, h - r + 1, wd - s + 1, r, s),
                         a.dtype, implementation, bk=bk)
    return _conv2d.conv2d(a, w, interpret=interp, **blk)


def attention(q, k, v, *, causal: bool = True, softcap: float = 0.0,
              window: int = 0, scale: float | None = None,
              bq: int = 128, bkv: int = 128, implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        # chunked online-softmax with flash-style custom VJP:
        # O(S·chunk) memory forward AND backward, same semantics/FLOPs
        from . import xla_attention
        return xla_attention.attention(
            q, k, v, causal=causal, softcap=softcap, window=window,
            scale=scale)
    return _flash.flash_attention(q, k, v, causal=causal, softcap=softcap,
                                  window=window, scale=scale, bq=bq,
                                  bkv=bkv, interpret=interp)


def rwkv6(r, k, v, w, u, state=None, *, chunk: int = 16,
          implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        from . import xla_linear
        return xla_linear.rwkv6(r, k, v, w, u, state)
    return _rwkv6.rwkv6(r, k, v, w, u, state, chunk=chunk, interpret=interp)


def mamba2(x, a, b, c, state=None, *, chunk: int = 64,
           implementation: str = "xla"):
    use_pallas, interp = _mode(implementation)
    if not use_pallas:
        from . import xla_linear
        return xla_linear.mamba2(x, a, b, c, state)
    return _mamba2.mamba2(x, a, b, c, state, chunk=chunk, interpret=interp)


def tuned_matmul(a, b, app: str = "default", implementation: str = "xla"):
    """GEMM with HASCO-tuned block shapes — the paper's technique as a
    first-class framework feature.  Shape-exact tuning-db records win;
    otherwise the app's co-designed accelerator from the solution registry
    sizes the blocks; otherwise the safe defaults."""
    from repro.core.solution import kernel_blocks

    shape = (a.shape[0], b.shape[1], a.shape[1])
    bm, bn, bk = kernel_blocks(app)
    blk = {"bm": bm, "bn": bn, "bk": bk}
    blk.update(_db_best("gemm", shape, a.dtype, implementation))
    return matmul(a, b, implementation=implementation, **blk)
