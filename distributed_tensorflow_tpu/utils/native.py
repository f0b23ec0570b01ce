"""Shared build-and-load for the in-tree C++ components.

Both native libraries (the coordination service, ``src/coordination``, and
the BPE tokenizer core, ``src/tokenizer``) follow one pattern: compile the
single-file source with ``g++`` on first use (or when the source's content
has changed since the cached .so was built) and load it over ctypes — no
pybind11 in the image.

The compile is multi-process safe: every builder writes to a per-pid temp
path and ``os.replace``s it into place (atomic on POSIX), so concurrent
workers starting on a fresh checkout never observe a partially linked
library; the last finished build wins with identical bytes.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

_DEFAULT_FLAGS = ("-O2", "-std=c++17", "-fPIC", "-Wall", "-shared")


def _keyed_lib_path(lib_path: str, src: str) -> str:
    """The binary's real location: ``<base>.<crc32 of the source><ext>``,
    next to ``lib_path`` when that directory is writable (the checkout
    layout), else under a per-user cache dir (a wheel in read-only
    site-packages still builds and runs).

    Keying the NAME on source content is what makes staleness impossible:
    a binary built from other source bytes has another name, whatever the
    two files' mtimes say (a copied tree does not promise to keep them in
    order), and two environments holding different package versions never
    share or clobber one binary."""
    if os.path.exists(lib_path) and not os.path.exists(src):
        # Prebuilt .so shipped without its source (e.g. a stripped wheel):
        # nothing to CRC and nothing to rebuild.
        return lib_path
    import zlib
    with open(src, "rb") as fh:
        tag = format(zlib.crc32(fh.read()), "08x")
    d = os.path.dirname(lib_path)
    if not os.access(d, os.W_OK):
        d = os.path.join(
            os.environ.get("XDG_CACHE_HOME",
                           os.path.join(os.path.expanduser("~"), ".cache")),
            "distributed_tensorflow_tpu")
        os.makedirs(d, exist_ok=True)
    base, ext = os.path.splitext(os.path.basename(lib_path))
    return os.path.join(d, f"{base}.{tag}{ext}")


def build_and_load(lib_path: str, src: str,
                   extra_flags: tuple[str, ...] = ()) -> ctypes.CDLL:
    """Compile ``src`` unless a binary of exactly these source bytes
    exists, then CDLL it.  Raises OSError/CalledProcessError on build or
    load failure; no caller falls back to another implementation.
    """
    lib_path = _keyed_lib_path(lib_path, src)
    if not os.path.exists(lib_path):
        tmp = f"{lib_path}.tmp.{os.getpid()}"
        try:
            subprocess.run(
                ["g++", *(_DEFAULT_FLAGS + tuple(extra_flags)),
                 "-o", tmp, src],
                check=True, capture_output=True)
            os.replace(tmp, lib_path)
        except subprocess.CalledProcessError as e:
            e.add_note(e.stderr.decode(errors="replace"))
            raise
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    return ctypes.CDLL(lib_path)
