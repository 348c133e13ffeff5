"""Native runtime components (C), self-building with graceful fallback.

The compute path compiles through XLA/Mosaic; the host runtime's hot
loops compile here. First import compiles ``framing.c`` with the
in-image toolchain into the package directory (~1 s, once); when no
compiler or a read-only checkout is available — or ``IG_TPU_NATIVE=0``
— ``framing`` is None and callers use their pure-Python twins
(netio/client.py keeps byte-identical behavior either way; the parity
suite in tests/test_native_framing.py pins it). ``status`` says which of
the two is active, and the fall-back is logged once, here.

The binary is never committed and its name carries a hash of
``framing.c``, so a binary built from other source — or one whose
mtime a copy of the tree scrambled — can never be loaded in its place.
"""

from __future__ import annotations

import hashlib
import importlib.util
import logging
import os
import pathlib
import shutil
import subprocess
import sysconfig

_DIR = pathlib.Path(__file__).resolve().parent


def _compile() -> pathlib.Path:
    source = _DIR / "framing.c"
    out = _DIR / f"_framing-{hashlib.sha1(source.read_bytes()).hexdigest()[:12]}.so"
    if out.exists():
        return out
    cc = os.environ.get("CC") or shutil.which("cc") or shutil.which("gcc") or shutil.which("g++")
    if cc is None:
        raise OSError("no C compiler found")
    include = sysconfig.get_paths()["include"]
    # Compile to a per-process temp name, then atomically rename:
    # concurrent workers on a fresh checkout must never dlopen a
    # half-written .so (os.replace is atomic on the same filesystem;
    # the losers just overwrite with identical bytes).
    tmp = _DIR / f"_framing.{os.getpid()}.tmp.so"
    try:
        subprocess.run(
            [cc, "-O2", "-shared", "-fPIC", f"-I{include}", str(source), "-o", str(tmp)],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
    finally:
        tmp.unlink(missing_ok=True)
    return out


def _load():
    """(module | None, status). Any failure to build or load falls back
    to the Python twin — host code, byte-identical by test."""
    if os.environ.get("IG_TPU_NATIVE", "1") == "0":
        return None, "python twin (IG_TPU_NATIVE=0)"
    try:
        so = _compile()
        spec = importlib.util.spec_from_file_location(
            "inference_gateway_tpu.native._framing", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    except Exception as e:  # never let the native path break imports
        return None, f"python twin ({type(e).__name__}: {e})"
    return mod, "native"


framing, status = _load()
if framing is None:
    logging.getLogger(__name__).warning("native framer not in use: %s", status)
