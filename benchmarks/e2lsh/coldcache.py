"""Cold-cache control for the spilled index, copied from the program's
``storage/measure.py`` cold mode so the method cannot move: flush the file's
dirty pages, ask the kernel to drop its page-cache pages
(``POSIX_FADV_DONTNEED``), and report how much of it is still resident
(``mincore``), so reads that miss the store's own cache reach the disk."""
from __future__ import annotations

import ctypes
import mmap
import os

import numpy as np


def drop_page_cache(path) -> bool:
    """fsync, then evict ``path``'s pages. False where unsupported."""
    if not hasattr(os, "posix_fadvise"):
        return False
    fd = os.open(os.fspath(path), os.O_RDONLY)
    try:
        os.fsync(fd)
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        return True
    except OSError:
        return False
    finally:
        os.close(fd)


def residency(path) -> float:
    """Share of ``path``'s pages resident in the page cache (nan where
    ``mincore`` cannot be asked)."""
    size = os.path.getsize(path)
    if size == 0:
        return 0.0
    npages = -(-size // mmap.PAGESIZE)
    with open(path, "rb") as f:
        try:
            mm = mmap.mmap(f.fileno(), size, flags=mmap.MAP_PRIVATE,
                           prot=mmap.PROT_READ | mmap.PROT_WRITE)
        except (ValueError, OSError):
            return float("nan")
    try:
        vec = (ctypes.c_ubyte * npages)()
        addr = ctypes.addressof(ctypes.c_char.from_buffer(mm))
        libc = ctypes.CDLL(None, use_errno=True)
        libc.mincore.argtypes = (ctypes.c_void_p, ctypes.c_size_t,
                                 ctypes.POINTER(ctypes.c_ubyte))
        libc.mincore.restype = ctypes.c_int
        if libc.mincore(addr, size, vec) != 0:
            return float("nan")
        return float(np.mean(np.frombuffer(vec, np.uint8) & 1))
    finally:
        vec = None          # the from_buffer export pins the map until freed
        mm = None
