"""Build the native runtime core on demand.

The reference builds its native substrate as one static-linked .so through a
Maven→Ant→CMake pipeline (SURVEY.md §2.3 "Build pipeline"); here the native
surface is small enough that a direct g++ invocation, cached by source mtime,
keeps the repo self-contained and hermetic (no network, no generators). The
.so is rebuilt automatically whenever a source file changes.
"""
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()

_SOURCES = {
    "resource_adaptor": ["resource_adaptor.cpp"],
    "parquet_footer": ["parquet_footer.cpp"],
    "parquet_reader": ["parquet_reader.cpp"],
    # standalone Arrow C Data Interface consumer: proves the export_to_c
    # binding surface is consumable by a non-Python runtime (zero-copy)
    "arrow_c_consumer": ["arrow_c_consumer.cpp"],
}

# extra link flags per lib (page decompression codecs; libsnappy/libzstd ship
# no dev symlink in this image, hence the -l: literal forms)
_LDFLAGS = {
    "parquet_reader": ["-lz", "-l:libzstd.so.1", "-l:libsnappy.so.1"],
}

# one flag list for build() AND check_warnings(): the nightly warning gate
# must compile exactly what ships or its diagnostics are for different code
_BASE_CMD = ["g++", "-std=c++17", "-O2", "-g", "-fPIC", "-shared",
             "-pthread", "-Wall", "-Wextra"]


def lib_path(name: str) -> str:
    return os.path.join(_HERE, f"lib{name}.so")


def check_warnings() -> list:
    """Compile every native lib fresh with the REAL build flags (same -O2
    etc. as build(), so optimizer-dependent diagnostics like
    -Wmaybe-uninitialized can fire) plus -Wall -Wextra, and return the
    diagnostics for any lib that warns (empty = clean). ci/nightly.sh
    fails on a non-empty result, so new warnings in load-bearing native
    code cannot silently accumulate. Output goes to a temp file: the
    cached .so files and their mtimes are untouched."""
    import tempfile
    out = []
    with tempfile.TemporaryDirectory() as tmp:
        for name, srcs in _SOURCES.items():
            cmd = _BASE_CMD + \
                ["-o", os.path.join(tmp, f"lib{name}.so")] + \
                [os.path.join(_HERE, s) for s in srcs] + \
                _LDFLAGS.get(name, [])
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                out.append(f"{name}: compile failed:\n{proc.stderr}")
            elif "warning:" in proc.stderr:
                out.append(f"{name}:\n{proc.stderr}")
    return out


def build(name: str) -> str:
    """Compile lib<name>.so from its sources if stale; return its path.

    The sanitizer tier does NOT go through here: ci/sanitizer.sh compiles
    the same sources into a native test driver with ASan+UBSan and runs it
    directly (sanitizing through the interpreter trips ASan's interceptor
    init when only the .so is instrumented)."""
    srcs = [os.path.join(_HERE, s) for s in _SOURCES[name]]
    out = lib_path(name)
    with _LOCK:
        if os.path.exists(out) and all(
                os.path.getmtime(out) >= os.path.getmtime(s) for s in srcs):
            return out
        cmd = _BASE_CMD + ["-o", out] + srcs + _LDFLAGS.get(name, [])
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"native build of {name} failed:\n{proc.stderr}")
        return out
