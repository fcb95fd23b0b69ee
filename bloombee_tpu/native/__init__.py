"""Native (C++) runtime components, loaded via ctypes.

Compiled lazily on first use with the system toolchain and cached under
~/.cache/bloombee_tpu, keyed by a hash of the source; every caller must
tolerate `None` (pure-Python fallback) so the framework works on
toolchain-less hosts. The fallback is host-only and silent at the call
site; `loaded()` says which is in use (a server logs it at start-up).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import pathlib
import subprocess

logger = logging.getLogger(__name__)

_SRC_DIR = pathlib.Path(__file__).parent
_CACHE = pathlib.Path.home() / ".cache" / "bloombee_tpu"

_byte_split_lib = None
_tried = False


def _build(src: pathlib.Path) -> pathlib.Path | None:
    code = src.read_bytes()
    tag = hashlib.sha1(code).hexdigest()[:12]
    out = _CACHE / f"{src.stem}-{tag}.so"
    if out.exists():
        return out
    _CACHE.mkdir(parents=True, exist_ok=True)
    # build to a process-unique temp path, then rename atomically so
    # concurrent processes never dlopen a half-written .so
    import os

    tmp = out.with_suffix(f".tmp-{os.getpid()}")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", str(src), "-o", str(tmp)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, out)
        return out
    except Exception as e:
        logger.info("native build failed (%s); using numpy fallback", e)
        tmp.unlink(missing_ok=True)
        return None


_paged_lib = None
_paged_tried = False


def paged_table_lib():
    """ctypes handle to the native paged table, or None."""
    global _paged_lib, _paged_tried
    if _paged_tried:
        return _paged_lib
    _paged_tried = True
    so = _build(_SRC_DIR / "paged_table.cc")
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(str(so))
        i64, i32p = ctypes.c_int64, ctypes.POINTER(ctypes.c_int32)
        sigs = {
            "pt_create": ([i64, i64], i64),
            "pt_destroy": ([i64], None),
            "pt_free_pages": ([i64], i64),
            "pt_add_seq": ([i64, i64], i64),
            "pt_has_seq": ([i64, i64], i64),
            "pt_drop_seq": ([i64, i64], i64),
            "pt_l_acc": ([i64, i64], i64),
            "pt_l_seq": ([i64, i64], i64),
            "pt_num_seq_pages": ([i64, i64], i64),
            "pt_assign_write_slots": (
                [i64, i64, i64, ctypes.c_int32, i32p], i64,
            ),
            "pt_commit": ([i64, i64, i64], i64),
            "pt_accept": ([i64, i64, i64], i64),
            "pt_rollback": ([i64, i64], i64),
            "pt_truncate_speculative": ([i64, i64, i64], i64),
            "pt_reset_seq": ([i64, i64], i64),
            "pt_restore_committed": ([i64, i64, i64], i64),
            "pt_page_row": ([i64, i64, i32p, i64], i64),
            "pt_range_slots": ([i64, i64, i64, i64, i32p], i64),
        }
        for name, (args, res) in sigs.items():
            fn = getattr(lib, name)
            fn.argtypes = args
            fn.restype = res
        _paged_lib = lib
    except Exception as e:  # pragma: no cover
        logger.info("native load failed (%s); using python table", e)
    return _paged_lib


def byte_split_lib():
    """ctypes handle to the byte-split codec, or None."""
    global _byte_split_lib, _tried
    if _tried:
        return _byte_split_lib
    _tried = True
    so = _build(_SRC_DIR / "byte_split.cc")
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(str(so))
        for fn in ("byte_split_2", "byte_merge_2"):
            getattr(lib, fn).argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ]
            getattr(lib, fn).restype = None
        _byte_split_lib = lib
    except Exception as e:  # pragma: no cover
        logger.info("native load failed (%s); using numpy fallback", e)
    return _byte_split_lib


def loaded() -> dict[str, bool]:
    """Which native components this process runs on (False = the numpy /
    pure-Python fallback). Builds or loads them if nothing has yet."""
    return {
        "paged_table": paged_table_lib() is not None,
        "byte_split": byte_split_lib() is not None,
    }
