"""ctypes bindings to the native runtime (cpp/ → build/libtpurpc.so).

The C++ half is the host runtime (fibers, sockets, protocols — ARCHITECTURE.md);
these bindings are how the Python data plane hands payloads to it.  Builds the
library on demand, from cpp/ as it stands, unless the one in build/ is stamped
as built from the same bytes.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

_REPO = pathlib.Path(__file__).resolve().parent.parent.parent
_BUILD = _REPO / "build"
_LIB_PATH = _BUILD / "libtpurpc.so"
_STAMP_PATH = _BUILD / "libtpurpc.so.sources.json"
_lock = threading.Lock()
_lib = None

# Canonical mirror of the C++ runtime's error-code table (the
# `constexpr int kE* = NNNN;` constants in cpp/net/*.h).  The
# error-code-sync rule in tools/lint_trpc.py keeps the two in lockstep —
# a code added or renumbered on one side only fails tier-1 instead of
# silently mis-typing exceptions.  The typed-exception constructors in
# client.py / kv.py / naming.py / collective.py resolve codes through
# the runtime capi at call time; this table is the build-time contract.
ERROR_CODES = {
    "kELimit": 2004,
    "kEOverloaded": 2005,
    "kEDraining": 2006,
    "kEDeadlineExpired": 2007,
    "kEKvMiss": 2101,
    "kEKvStale": 2102,
    "kEKvExists": 2103,
    "kENamingStaleEpoch": 2111,
    "kENamingMiss": 2112,
    "kECollAbort": 2121,
    "kECollEpoch": 2122,
    "kECollMismatch": 2123,
}


def _source_manifest() -> dict[str, str]:
    """sha1 of every build input under cpp/, by repo-relative path."""
    out = {}
    for path in sorted((_REPO / "cpp").rglob("*")):
        if path.suffix in (".cc", ".h", ".inc", ".S", ".txt") and path.is_file():
            out[str(path.relative_to(_REPO))] = hashlib.sha1(
                path.read_bytes()).hexdigest()
    return out


def _read_stamp() -> dict | None:
    """What build/libtpurpc.so was built from, or None when there is no
    library or nothing trustworthy says where it came from."""
    if not _LIB_PATH.exists():
        return None
    try:
        stamp = json.loads(_STAMP_PATH.read_text())
        return stamp if isinstance(stamp["sources"], dict) else None
    except (OSError, ValueError, KeyError, TypeError):
        return None


def _run_tool(cmd: list[str]) -> None:
    # Surface the tool's diagnostics: a bare CalledProcessError with
    # swallowed output is undiagnosable from an import failure.
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError as e:
        sys.stderr.write(f"build step failed: {' '.join(cmd[:4])} ...\n"
                         f"{e.stdout[-4000:]}\n{e.stderr[-8000:]}\n")
        raise


def _build_with_compiler() -> None:
    """cmake-less recipe: compile cpp/ straight with the system C++
    compiler (same flags as cpp/CMakeLists.txt) into build/obj/ and link
    libtpurpc.so.  Keeps the Python suite alive on minimal images that
    bake a toolchain but no cmake; the C++ unit BINARIES still need the
    cmake build (tests/test_cpp.py skips them instead)."""
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        raise FileNotFoundError(
            "neither cmake nor a C++ compiler available to build "
            "libtpurpc.so"
        )
    cpp = _REPO / "cpp"
    obj_dir = _BUILD / "obj"
    obj_dir.mkdir(parents=True, exist_ok=True)
    sources: list[pathlib.Path] = []
    for sub, pats in (
        ("base", ("*.cc",)),
        ("fiber", ("*.cc", "*.S")),
        ("stat", ("*.cc",)),
        ("net", ("*.cc",)),
        ("capi", ("*.cc",)),
    ):
        for pat in pats:
            sources.extend(sorted((cpp / sub).glob(pat)))
    flags = [
        "-std=c++20", "-fPIC", "-O2", "-g", "-Wall", "-Wextra",
        "-Wno-unused-parameter", "-fno-omit-frame-pointer", "-I", str(cpp),
    ]
    # A header edit invalidates every object (no dependency scanning here;
    # conservative and correct).
    newest_h = 0.0
    for pat in ("*.h", "*.inc"):
        for p in cpp.rglob(pat):
            newest_h = max(newest_h, p.stat().st_mtime)

    def compile_one(src: pathlib.Path) -> str:
        obj = obj_dir / (
            str(src.relative_to(cpp)).replace("/", "_") + ".o"
        )
        if (
            not obj.exists()
            or obj.stat().st_mtime < max(src.stat().st_mtime, newest_h)
        ):
            _run_tool([cxx, *flags, "-c", str(src), "-o", str(obj)])
        return str(obj)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as pool:
        objs = list(pool.map(compile_one, sources))
    _run_tool(
        [cxx, "-shared", "-o", str(_LIB_PATH), *objs,
         "-lpthread", "-lrt", "-lz", "-ldl"]
    )


def ensure_built(all_targets: bool = False) -> dict:
    """(Re)build the native library unless build/libtpurpc.so is stamped
    as built from exactly the bytes now under cpp/.  The stamp is keyed on
    content, not mtimes: a copy or checkout resets mtimes, and a library
    that something else left in build/ must never be loaded.  Shared by
    the bindings and the pytest fixture so there is one build recipe.
    Without cmake, falls back to a direct compiler build of the library
    alone (all_targets callers must check for cmake/ctest themselves and
    skip).  Returns {"recipe": "reused" | "cmake" | "compiler",
    "seconds": wall time of the build}.  One process at a time: pytest's
    workers share build/, and in a fresh checkout each of them would
    start a `--clean-first` build under the others."""
    _BUILD.mkdir(parents=True, exist_ok=True)
    with open(_BUILD / ".ensure_built.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when the file closes
        return _ensure_built_locked(all_targets)


def _ensure_built_locked(all_targets: bool) -> dict:
    sources = _source_manifest()
    stamp = _read_stamp()
    have_cmake = shutil.which("cmake") is not None
    if stamp is not None and stamp["sources"] == sources and (
            stamp.get("all_targets") or not all_targets or not have_cmake):
        return {"recipe": "reused", "seconds": 0.0}
    t0 = time.perf_counter()
    if stamp is not None:
        # make and the direct recipe decide by mtime; make the mtimes say
        # what the content says, so the build stays incremental.
        for rel, digest in sources.items():
            if stamp["sources"].get(rel) != digest:
                os.utime(_REPO / rel)
    jobs = str(os.cpu_count() or 4)
    if have_cmake:
        _run_tool(["cmake", "-S", str(_REPO / "cpp"), "-B", str(_BUILD)])
        cmd = ["cmake", "--build", str(_BUILD), "-j", jobs]
        if not all_targets:
            cmd += ["--target", "tpurpc"]
        if stamp is None:  # objects of unknown origin: reuse none of them
            cmd += ["--clean-first"]
        _run_tool(cmd)
    else:
        if stamp is None:
            shutil.rmtree(_BUILD / "obj", ignore_errors=True)
        _build_with_compiler()
    _STAMP_PATH.write_text(json.dumps(
        {"sources": sources, "all_targets": all_targets and have_cmake}))
    return {"recipe": "cmake" if have_cmake else "compiler",
            "seconds": round(time.perf_counter() - t0, 1)}


_ensure_built = ensure_built


def load_library() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            _ensure_built()
            lib = ctypes.CDLL(str(_LIB_PATH))
            lib.trpc_iobuf_create.restype = ctypes.c_void_p
            lib.trpc_channel_create_ex.restype = ctypes.c_void_p
            lib.trpc_channel_create_ex.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
                ctypes.c_int,
            ]
            lib.trpc_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
            lib.trpc_flag_get.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_iobuf_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_iobuf_append.argtypes = [
                ctypes.c_void_p,
                ctypes.c_char_p,
                ctypes.c_size_t,
            ]
            lib.trpc_iobuf_size.argtypes = [ctypes.c_void_p]
            lib.trpc_iobuf_size.restype = ctypes.c_size_t
            lib.trpc_iobuf_copy_to.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_size_t,
                ctypes.c_size_t,
            ]
            lib.trpc_iobuf_copy_to.restype = ctypes.c_size_t
            lib.trpc_iobuf_cutn.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_size_t,
            ]
            lib.trpc_iobuf_cutn.restype = ctypes.c_size_t
            lib.trpc_iobuf_pop_front.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
            lib.trpc_iobuf_pop_front.restype = ctypes.c_size_t
            lib.trpc_iobuf_block_count.argtypes = [ctypes.c_void_p]
            lib.trpc_iobuf_block_count.restype = ctypes.c_size_t
            lib.trpc_iobuf_block_ptr.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t,
            ]
            lib.trpc_iobuf_block_ptr.restype = ctypes.c_void_p
            lib.trpc_endpoint_parse.argtypes = [
                ctypes.c_char_p,
                ctypes.c_char_p,
                ctypes.c_size_t,
            ]
            lib.trpc_endpoint_parse.restype = ctypes.c_int
            # Zero-copy surface (capi/base_capi.cc).  Explicit marshalling
            # for every pointer-crossing entry — tools/lint_trpc.py's
            # capi-gil rule gates this: a missing restype silently
            # truncates a 64-bit pointer/size_t.
            lib.trpc_iobuf_append_user_data.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_void_p,  # deleter fn ptr (CFUNCTYPE or None)
                ctypes.c_void_p,
            ]
            lib.trpc_iobuf_append_user_data.restype = None
            lib.trpc_channel_call_buf.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_channel_call_buf.restype = ctypes.c_int
            # Native full-stack echo loop (capi/rpc_capi.cc): data, len,
            # iters, concurrency, transport, resp_out, out_gbps,
            # transport_used + len, err + len.
            lib.trpc_bench_echo_rpc.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
                ctypes.c_int, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_double), ctypes.c_char_p,
                ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_bench_echo_rpc.restype = ctypes.c_int
            # One-sided RMA regions + kernel probe (capi/rpc_capi.cc;
            # net/rma.h, base/proc.h).
            lib.trpc_rma_alloc.argtypes = [
                ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_rma_alloc.restype = ctypes.c_void_p
            lib.trpc_rma_free.argtypes = [ctypes.c_void_p]
            lib.trpc_rma_free.restype = None
            lib.trpc_rma_reg.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
            lib.trpc_rma_reg.restype = ctypes.c_uint64
            lib.trpc_rma_unreg.argtypes = [ctypes.c_uint64]
            lib.trpc_rma_unreg.restype = ctypes.c_int
            lib.trpc_rma_region_count.argtypes = []
            lib.trpc_rma_region_count.restype = ctypes.c_size_t
            lib.trpc_kernel_supports.argtypes = [ctypes.c_char_p]
            lib.trpc_kernel_supports.restype = ctypes.c_int
            # Paged KV-block registry (capi/kv_capi.cc; net/kvstore.h).
            lib.trpc_server_enable_kv_registry.argtypes = [ctypes.c_void_p]
            lib.trpc_server_enable_kv_registry.restype = ctypes.c_int
            lib.trpc_server_enable_kv_store.argtypes = [ctypes.c_void_p]
            lib.trpc_server_enable_kv_store.restype = ctypes.c_int
            lib.trpc_kv_publish.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint64,
                ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_publish.restype = ctypes.c_int
            lib.trpc_kv_publish_ex.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint64,
                ctypes.c_int64, ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_publish_ex.restype = ctypes.c_int
            lib.trpc_kv_withdraw.argtypes = [ctypes.c_uint64]
            lib.trpc_kv_withdraw.restype = ctypes.c_int
            lib.trpc_kv_renew.argtypes = [ctypes.c_uint64, ctypes.c_int64]
            lib.trpc_kv_renew.restype = ctypes.c_int
            lib.trpc_kv_store_count.argtypes = []
            lib.trpc_kv_store_count.restype = ctypes.c_size_t
            lib.trpc_kv_store_bytes_used.argtypes = []
            lib.trpc_kv_store_bytes_used.restype = ctypes.c_uint64
            lib.trpc_kv_registry_count.argtypes = []
            lib.trpc_kv_registry_count.restype = ctypes.c_size_t
            lib.trpc_kv_codes.argtypes = [
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.trpc_kv_codes.restype = None
            lib.trpc_kv_reset.argtypes = []
            lib.trpc_kv_reset.restype = None
            lib.trpc_kv_note_fetch_many.argtypes = [ctypes.c_uint64]
            lib.trpc_kv_note_fetch_many.restype = None
            lib.trpc_kv_note_sequence.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_int]
            lib.trpc_kv_note_sequence.restype = None
            lib.trpc_kv_note_publish.argtypes = [ctypes.c_uint64,
                                                 ctypes.c_uint64]
            lib.trpc_kv_note_publish.restype = None
            # Whether bytes lie in a landing block the store can publish
            # from where they are (capi/hostpool_capi.cc).
            lib.trpc_host_pool_holds.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_size_t]
            lib.trpc_host_pool_holds.restype = ctypes.c_int
            # Content-addressed prefix cache (capi/kv_capi.cc; ISSUE 17).
            lib.trpc_kv_content_hash.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_content_hash.restype = None
            lib.trpc_kv_content_hash_lanes.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_uint64)),
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_content_hash_lanes.restype = None
            lib.trpc_kv_prefix_chain.argtypes = [
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
                ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_size_t,
            ]
            lib.trpc_kv_prefix_chain.restype = ctypes.c_size_t
            lib.trpc_kv_prefix_publish_at.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32,
                ctypes.c_void_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_size_t,
                ctypes.c_int64, ctypes.c_uint64, ctypes.c_int,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_prefix_publish_at.restype = ctypes.c_int
            lib.trpc_kv_prefix_publish_run.argtypes = [
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_int), ctypes.c_size_t,
                ctypes.c_int64, ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_prefix_publish_run.restype = ctypes.c_size_t
            lib.trpc_kv_prefix_publish_state.argtypes = (
                lib.trpc_kv_prefix_publish_at.argtypes[:7]
                + [ctypes.c_int64, ctypes.c_int]
                + [ctypes.POINTER(ctypes.c_uint64)] * 5)
            lib.trpc_kv_prefix_publish_state.restype = ctypes.c_int
            lib.trpc_kv_prefix_withdraw.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.trpc_kv_prefix_withdraw.restype = ctypes.c_int
            lib.trpc_kv_prefix_store_count.argtypes = []
            lib.trpc_kv_prefix_store_count.restype = ctypes.c_size_t
            lib.trpc_kv_prefix_hot_bytes.argtypes = []
            lib.trpc_kv_prefix_hot_bytes.restype = ctypes.c_uint64
            lib.trpc_kv_prefix_cold_bytes.argtypes = []
            lib.trpc_kv_prefix_cold_bytes.restype = ctypes.c_uint64
            lib.trpc_kv_prefix_registry_count.argtypes = []
            lib.trpc_kv_prefix_registry_count.restype = ctypes.c_size_t
            lib.trpc_kv_prefix_registry_replicas.argtypes = []
            lib.trpc_kv_prefix_registry_replicas.restype = ctypes.c_size_t
            lib.trpc_kv_prefix_counters.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_kv_prefix_counters.restype = None
            # Cluster control plane (capi/naming_capi.cc; net/naming.h):
            # naming registry + graceful drain / hot-restart handoff.
            lib.trpc_server_enable_naming.argtypes = [ctypes.c_void_p]
            lib.trpc_server_enable_naming.restype = ctypes.c_int
            lib.trpc_server_announce.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_char_p, ctypes.c_int,
            ]
            lib.trpc_server_announce.restype = ctypes.c_int
            lib.trpc_server_drain.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p,
            ]
            lib.trpc_server_drain.restype = ctypes.c_int
            lib.trpc_server_start_handoff.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ]
            lib.trpc_server_start_handoff.restype = ctypes.c_int
            lib.trpc_server_draining.argtypes = [ctypes.c_void_p]
            lib.trpc_server_draining.restype = ctypes.c_int
            lib.trpc_draining_code.argtypes = []
            lib.trpc_draining_code.restype = ctypes.c_int
            lib.trpc_naming_codes.argtypes = [
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ]
            lib.trpc_naming_codes.restype = None
            lib.trpc_naming_member_count.argtypes = [ctypes.c_char_p]
            lib.trpc_naming_member_count.restype = ctypes.c_size_t
            lib.trpc_naming_reset.argtypes = []
            lib.trpc_naming_reset.restype = None
            lib.trpc_kv_withdraw_all.argtypes = []
            lib.trpc_kv_withdraw_all.restype = ctypes.c_size_t
            lib.trpc_rma_spans_in_use.argtypes = []
            lib.trpc_rma_spans_in_use.restype = ctypes.c_size_t
            # Collective transfer schedules (capi/coll_capi.cc;
            # net/collective.h): group put plans over the RMA fabric.
            lib.trpc_server_enable_collective.argtypes = [ctypes.c_void_p]
            lib.trpc_server_enable_collective.restype = ctypes.c_int
            lib.trpc_coll_group_create.argtypes = [
                ctypes.c_char_p, ctypes.c_uint32, ctypes.c_int64,
                ctypes.c_int,
            ]
            lib.trpc_coll_group_create.restype = ctypes.c_void_p
            lib.trpc_coll_group_create_naming.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_int,
            ]
            lib.trpc_coll_group_create_naming.restype = ctypes.c_void_p
            lib.trpc_coll_group_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_coll_group_destroy.restype = None
            lib.trpc_coll_group_rank.argtypes = [ctypes.c_void_p]
            lib.trpc_coll_group_rank.restype = ctypes.c_uint32
            lib.trpc_coll_group_size.argtypes = [ctypes.c_void_p]
            lib.trpc_coll_group_size.restype = ctypes.c_uint32
            lib.trpc_coll_group_version.argtypes = [ctypes.c_void_p]
            lib.trpc_coll_group_version.restype = ctypes.c_uint64
            lib.trpc_coll_run.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
                ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.trpc_coll_run.restype = ctypes.c_int
            # Overlap-aware path: trpc_coll_run + a readiness-map handle
            # over the caller's send buffer (ISSUE 18).
            lib.trpc_coll_run_ready.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
                ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.trpc_coll_run_ready.restype = ctypes.c_int
            lib.trpc_coll_ready_create.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.trpc_coll_ready_create.restype = ctypes.c_uint64
            lib.trpc_coll_ready_stamp.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.trpc_coll_ready_stamp.restype = ctypes.c_int
            lib.trpc_coll_ready_destroy.argtypes = [ctypes.c_uint64]
            lib.trpc_coll_ready_destroy.restype = None
            lib.trpc_coll_ready_maps.argtypes = []
            lib.trpc_coll_ready_maps.restype = ctypes.c_size_t
            lib.trpc_coll_reshard_run.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_uint32, ctypes.c_uint64, ctypes.c_void_p,
                ctypes.c_uint64, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_uint64,
            ]
            lib.trpc_coll_reshard_run.restype = ctypes.c_int
            lib.trpc_coll_reshard_plan.argtypes = [
                ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint32,
                ctypes.c_uint64, ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint32),
            ]
            lib.trpc_coll_reshard_plan.restype = ctypes.c_int
            lib.trpc_coll_codes.argtypes = [
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.trpc_coll_codes.restype = None
            lib.trpc_coll_sessions.argtypes = []
            lib.trpc_coll_sessions.restype = ctypes.c_size_t
            lib.trpc_rma_scavenge.argtypes = []
            lib.trpc_rma_scavenge.restype = ctypes.c_size_t
            # RPC surface (capi/rpc_capi.cc).
            lib.trpc_server_create.restype = ctypes.c_void_p
            lib.trpc_server_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_server_register.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            lib.trpc_server_register.restype = ctypes.c_int
            lib.trpc_call_respond.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
                ctypes.c_int, ctypes.c_char_p,
            ]
            lib.trpc_call_respond.restype = ctypes.c_int
            lib.trpc_server_start.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.trpc_server_start.restype = ctypes.c_int
            lib.trpc_server_port.argtypes = [ctypes.c_void_p]
            lib.trpc_server_port.restype = ctypes.c_int
            lib.trpc_server_stop.argtypes = [ctypes.c_void_p]
            lib.trpc_channel_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
            lib.trpc_channel_create.restype = ctypes.c_void_p
            lib.trpc_channel_create_shm.argtypes = [
                ctypes.c_char_p, ctypes.c_int64,
            ]
            lib.trpc_channel_create_shm.restype = ctypes.c_void_p
            lib.trpc_channel_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_channel_transport.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_channel_call.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_size_t, ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_channel_call.restype = ctypes.c_int
            lib.trpc_cluster_create.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ]
            lib.trpc_cluster_create.restype = ctypes.c_void_p
            lib.trpc_cluster_create_ex.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_int, ctypes.c_int64, ctypes.c_char_p,
                ctypes.c_int64, ctypes.c_int64,
            ]
            lib.trpc_cluster_create_ex.restype = ctypes.c_void_p
            # Fault injection (cpp/net/fault.h).
            lib.trpc_fault_set.argtypes = [ctypes.c_char_p]
            lib.trpc_fault_set.restype = ctypes.c_int
            lib.trpc_fault_get.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
            lib.trpc_fault_get.restype = ctypes.c_int
            lib.trpc_fault_log.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
            lib.trpc_fault_log.restype = ctypes.c_size_t
            lib.trpc_fault_reset.argtypes = []
            lib.trpc_fault_injected.restype = ctypes.c_uint64
            lib.trpc_server_fault_set.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.trpc_server_fault_set.restype = ctypes.c_int
            # QoS subsystem (capi/qos_capi.cc; cpp/net/qos.h).
            lib.trpc_server_set_qos.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.trpc_server_set_qos.restype = ctypes.c_int
            lib.trpc_server_set_reuseport.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
            ]
            lib.trpc_server_set_reuseport.restype = ctypes.c_int
            lib.trpc_server_accept_counts.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_int,
            ]
            lib.trpc_server_accept_counts.restype = ctypes.c_int
            lib.trpc_channel_set_qos.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ]
            lib.trpc_cluster_set_qos.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ]
            lib.trpc_call_qos.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_call_qos.restype = ctypes.c_int
            lib.trpc_qos_overloaded_code.argtypes = []
            lib.trpc_qos_overloaded_code.restype = ctypes.c_int
            # Deadline & cancellation plane (capi/deadline_capi.cc;
            # cpp/net/deadline.h).
            lib.trpc_deadline_expired_code.argtypes = []
            lib.trpc_deadline_expired_code.restype = ctypes.c_int
            lib.trpc_call_remaining_us.argtypes = [ctypes.c_void_p]
            lib.trpc_call_remaining_us.restype = ctypes.c_int64
            lib.trpc_call_cancelled.argtypes = [ctypes.c_void_p]
            lib.trpc_call_cancelled.restype = ctypes.c_int
            lib.trpc_deadline_ambient_set.argtypes = [ctypes.c_int64]
            lib.trpc_deadline_ambient_set.restype = None
            lib.trpc_deadline_ambient_remaining.argtypes = []
            lib.trpc_deadline_ambient_remaining.restype = ctypes.c_int64
            lib.trpc_deadline_ambient_clear.argtypes = []
            lib.trpc_deadline_ambient_clear.restype = None
            lib.trpc_cancel_registered.argtypes = []
            lib.trpc_cancel_registered.restype = ctypes.c_size_t
            lib.trpc_deadline_ensure_registered.argtypes = []
            lib.trpc_deadline_ensure_registered.restype = None
            lib.trpc_qos_lane_depth.argtypes = [ctypes.c_int]
            lib.trpc_qos_lane_depth.restype = ctypes.c_int64
            # Batched async pipeline (capi/batch_capi.cc).
            lib.trpc_batch_create.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.trpc_batch_create.restype = ctypes.c_void_p
            lib.trpc_batch_submit.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.c_size_t, ctypes.c_int64,
                ctypes.c_void_p,  # deleter fn ptr (CFUNCTYPE or None)
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_batch_submit.restype = ctypes.c_size_t
            lib.trpc_batch_reserve.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_uint64)]
            lib.trpc_batch_reserve.restype = ctypes.c_size_t
            lib.trpc_batch_submit_staged.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_size_t),
                ctypes.c_size_t, ctypes.c_int64,
                ctypes.c_void_p,  # deleter fn ptr (CFUNCTYPE or None)
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_void_p,  # trpc_batch_stage[n] (batch.BatchStage)
            ]
            lib.trpc_batch_submit_staged.restype = ctypes.c_size_t
            lib.trpc_batch_poll.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_int64,
            ]
            lib.trpc_batch_poll.restype = ctypes.c_size_t
            lib.trpc_batch_cancel.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64,
            ]
            lib.trpc_batch_cancel.restype = ctypes.c_int
            lib.trpc_batch_outstanding.argtypes = [ctypes.c_void_p]
            lib.trpc_batch_outstanding.restype = ctypes.c_size_t
            lib.trpc_batch_inflight.argtypes = [ctypes.c_void_p]
            lib.trpc_batch_inflight.restype = ctypes.c_size_t
            lib.trpc_batch_quiesce.argtypes = [ctypes.c_void_p]
            lib.trpc_batch_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_server_register_echo.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.trpc_server_register_echo.restype = ctypes.c_int
            # Observability plane (capi/observe_capi.cc).
            lib.trpc_vars_dump.argtypes = [
                ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_vars_dump.restype = ctypes.c_size_t
            lib.trpc_var_read.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_var_read.restype = ctypes.c_int
            lib.trpc_latency_read.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_double),
            ]
            lib.trpc_latency_read.restype = ctypes.c_int
            lib.trpc_var_exists.argtypes = [ctypes.c_char_p]
            lib.trpc_var_exists.restype = ctypes.c_int
            lib.trpc_rpcz_dump.argtypes = [
                ctypes.c_size_t, ctypes.c_uint64, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_rpcz_dump.restype = ctypes.c_size_t
            # SLO engine + fleet observability (capi/slo_capi.cc;
            # stat/slo.h, net/naming.h fleet publication).
            lib.trpc_server_set_slo.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.trpc_server_set_slo.restype = ctypes.c_int
            lib.trpc_slo_dump.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_slo_dump.restype = ctypes.c_size_t
            lib.trpc_fleet_blob.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_fleet_blob.restype = ctypes.c_size_t
            lib.trpc_fleet_dump.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_fleet_dump.restype = ctypes.c_size_t
            lib.trpc_slo_enabled.restype = ctypes.c_int
            lib.trpc_slo_breach_total.restype = ctypes.c_uint64
            # Self-tuning controller + flag introspection
            # (capi/tuner_capi.cc; stat/tuner.h).
            lib.trpc_flags_dump.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_flags_dump.restype = ctypes.c_size_t
            lib.trpc_tuner_enabled.restype = ctypes.c_int
            lib.trpc_tuner_dump.argtypes = [
                ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_tuner_dump.restype = ctypes.c_size_t
            lib.trpc_tuner_counters.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_tuner_counters.restype = None
            lib.trpc_server_enable_tuner.argtypes = [ctypes.c_void_p]
            lib.trpc_server_enable_tuner.restype = ctypes.c_int
            lib.trpc_tuner_reset.argtypes = []
            lib.trpc_tuner_reset.restype = None
            # Traffic capture (capi/capture_capi.cc; stat/capture.h).
            lib.trpc_capture_enabled.restype = ctypes.c_int
            lib.trpc_capture_dump.argtypes = [
                ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_capture_dump.restype = ctypes.c_size_t
            lib.trpc_capture_dump_file.argtypes = [ctypes.c_char_p]
            lib.trpc_capture_dump_file.restype = ctypes.c_longlong
            lib.trpc_capture_counters.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_capture_counters.restype = None
            lib.trpc_capture_reset.argtypes = []
            lib.trpc_capture_reset.restype = None
            lib.trpc_trace_get.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_trace_set.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64,
            ]
            lib.trpc_trace_clear.argtypes = []
            lib.trpc_trace_new_id.restype = ctypes.c_uint64
            lib.trpc_span_start.argtypes = [ctypes.c_char_p, ctypes.c_int]
            lib.trpc_span_start.restype = ctypes.c_void_p
            lib.trpc_span_annotate.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.trpc_span_ids.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_span_end.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.trpc_latency_create.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p,
            ]
            lib.trpc_latency_create.restype = ctypes.c_void_p
            lib.trpc_latency_record.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
            ]
            lib.trpc_latency_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_gauge_create.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p,
            ]
            lib.trpc_gauge_create.restype = ctypes.c_void_p
            lib.trpc_gauge_set.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.trpc_gauge_add.argtypes = [ctypes.c_void_p, ctypes.c_int64]
            lib.trpc_gauge_add.restype = ctypes.c_int64
            lib.trpc_gauge_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_cluster_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_cluster_call.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_size_t, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_cluster_call.restype = ctypes.c_int
            # Cache-aware routing (capi/rpc_capi.cc; net/lb_hint.h).
            lib.trpc_cluster_call_hinted.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_size_t, ctypes.c_void_p, ctypes.c_uint64,
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_cluster_call_hinted.restype = ctypes.c_int
            lib.trpc_lb_hint_counters.argtypes = [
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.trpc_lb_hint_counters.restype = None
            # Streaming plane (capi/stream_capi.cc; net/stream.h; ISSUE 20).
            lib.trpc_stream_open.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_size_t, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_int, ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int), ctypes.c_char_p,
                ctypes.c_size_t,
            ]
            lib.trpc_stream_open.restype = ctypes.c_void_p
            lib.trpc_call_stream_accept.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
            ]
            lib.trpc_call_stream_accept.restype = ctypes.c_void_p
            lib.trpc_stream_read.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_int64,
            ]
            lib.trpc_stream_read.restype = ctypes.c_long
            lib.trpc_stream_next_len.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
            ]
            lib.trpc_stream_next_len.restype = ctypes.c_long
            lib.trpc_stream_write.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ]
            lib.trpc_stream_write.restype = ctypes.c_int
            lib.trpc_stream_write_user.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.trpc_stream_write_user.restype = ctypes.c_int
            lib.trpc_stream_unread_high_water.argtypes = [ctypes.c_void_p]
            lib.trpc_stream_unread_high_water.restype = ctypes.c_uint64
            lib.trpc_server_register_stream_echo.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.trpc_server_register_stream_echo.restype = ctypes.c_int
            lib.trpc_stream_close.argtypes = [ctypes.c_void_p]
            lib.trpc_stream_close.restype = ctypes.c_int
            lib.trpc_stream_destroy.argtypes = [ctypes.c_void_p]
            lib.trpc_stream_destroy.restype = None
            lib.trpc_stream_id.argtypes = [ctypes.c_void_p]
            lib.trpc_stream_id.restype = ctypes.c_uint64
            lib.trpc_stream_pending.argtypes = [ctypes.c_void_p]
            lib.trpc_stream_pending.restype = ctypes.c_size_t
            # Streamed-inference front door (capi/infer_capi.cc;
            # net/infer.h; ISSUE 20).
            lib.trpc_server_enable_infer.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p,
                ctypes.c_char_p,
            ]
            lib.trpc_server_enable_infer.restype = ctypes.c_void_p
            lib.trpc_infer_stop.argtypes = [ctypes.c_void_p]
            lib.trpc_infer_stop.restype = None
            lib.trpc_infer_dump.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ]
            lib.trpc_infer_dump.restype = ctypes.c_size_t
            lib.trpc_infer_streams_live.argtypes = [ctypes.c_void_p]
            lib.trpc_infer_streams_live.restype = ctypes.c_longlong
            lib.trpc_infer_streams_peak.argtypes = [ctypes.c_void_p]
            lib.trpc_infer_streams_peak.restype = ctypes.c_longlong
            _lib = lib
    return _lib


class IOBuf:
    """Python view of trpc::IOBuf (zero-copy chained buffer)."""

    def __init__(self, data: bytes | None = None):
        self._lib = load_library()
        self._ptr = self._lib.trpc_iobuf_create()
        if data:
            self.append(data)

    def __del__(self):
        ptr, self._ptr = getattr(self, "_ptr", None), None
        if ptr:
            self._lib.trpc_iobuf_destroy(ptr)

    def __len__(self) -> int:
        return self._lib.trpc_iobuf_size(self._ptr)

    def append(self, data: bytes) -> None:
        self._lib.trpc_iobuf_append(self._ptr, data, len(data))

    def to_bytes(self) -> bytes:
        n = len(self)
        out = ctypes.create_string_buffer(n)
        got = self._lib.trpc_iobuf_copy_to(self._ptr, out, n, 0)
        return out.raw[:got]

    def cutn(self, n: int) -> "IOBuf":
        out = IOBuf()
        self._lib.trpc_iobuf_cutn(self._ptr, out._ptr, n)
        return out

    def pop_front(self, n: int) -> int:
        return self._lib.trpc_iobuf_pop_front(self._ptr, n)

    @property
    def block_count(self) -> int:
        return self._lib.trpc_iobuf_block_count(self._ptr)


def parse_endpoint(addr: str) -> str:
    """Normalize 'host:port[/device]' via the native EndPoint parser."""
    lib = load_library()
    out = ctypes.create_string_buffer(64)
    if lib.trpc_endpoint_parse(addr.encode(), out, 64) != 0:
        raise ValueError(f"bad endpoint: {addr!r}")
    return out.value.decode()
