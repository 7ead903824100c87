#!/usr/bin/env python3
"""Probe: can a TPU device buffer (or pinned host staging) enter the IOBuf
path by pointer, the way RDMA lkeys do?

Parity target: /root/reference/src/butil/iobuf.h:257-264
(append_user_data_with_meta carrying RDMA lkeys) and
/root/reference/src/brpc/rdma/block_pool.cpp (registering memory once and
letting the transport ship references instead of bytes).  The ICI transport
(cpp/net/ici_transport.h) exposes `ici_set_slab_registrar` as the seam a
real device backend would plug into; this probe establishes what the
backend can actually get from the PJRT stack in this image.

Five attempts, most direct first:
  A. `arr.unsafe_buffer_pointer()`  — PJRT's raw device pointer accessor.
  B. `arr.__dlpack__()`             — DLPack export (device type + data ptr).
  C. `np.asarray(arr)`              — host staging copy (the fallback the
     zerocopy path documents); measures where the bytes land.
  D. jax.device_put with donation into a pre-registered numpy buffer —
     tests whether PJRT will adopt OUR registered slab as backing store
     (block_pool-style "allocator takeover").
  E. pointer-identity: if A or D produced a stable pointer, wrap it in an
     IOBuf user-data block via the C ABI and verify byte identity.

Each backend is probed in its own subprocess (the parent never imports
jax, so the TPU child is the one process that holds the chip).  Results
are written to tools/PJRT_PROBE.md so the conclusion is reproducible and
citable from PARITY.md.

Usage: python tools/pjrt_probe.py [--cpu]   (--cpu = probe the CPU backend
as a control; the CPU backend SHOULD yield real pointers, proving the
probe itself works.)
"""
import json
import os
import signal
import subprocess
import sys
import textwrap

CHILD = r"""
import ctypes, json, os, sys
out = {"backend": None, "attempts": {}}

force_cpu = os.environ.get("PROBE_CPU") == "1"
import jax
if force_cpu:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

dev = jax.devices()[0]
out["backend"] = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()), "jax": jax.__version__}

arr = jnp.arange(4096, dtype=jnp.uint8).reshape(64, 64)
arr = jax.device_put(arr, dev)
arr.block_until_ready()

# A. raw device pointer accessor
try:
    p = arr.unsafe_buffer_pointer()
    out["attempts"]["A_unsafe_buffer_pointer"] = {"ok": True, "ptr": hex(p)}
except Exception as e:  # noqa: BLE001
    out["attempts"]["A_unsafe_buffer_pointer"] = {
        "ok": False, "error": f"{type(e).__name__}: {e}"}

# B. DLPack export
try:
    cap = arr.__dlpack__()
    dldev = arr.__dlpack_device__()
    out["attempts"]["B_dlpack"] = {"ok": True, "dl_device": list(dldev),
                                   "capsule": str(cap)}
except Exception as e:  # noqa: BLE001
    out["attempts"]["B_dlpack"] = {"ok": False,
                                   "error": f"{type(e).__name__}: {e}"}

# C. host staging copy — where do the bytes land?
try:
    host = np.asarray(arr)
    out["attempts"]["C_host_staging"] = {
        "ok": True, "ptr": hex(host.ctypes.data),
        "writeable": bool(host.flags.writeable),
        "note": "device->host DMA into a fresh numpy buffer"}
except Exception as e:  # noqa: BLE001
    out["attempts"]["C_host_staging"] = {"ok": False,
                                         "error": f"{type(e).__name__}: {e}"}

# D. can PJRT adopt OUR buffer as backing store (allocator takeover)?
try:
    slab = np.zeros((64, 64), dtype=np.uint8)
    slab_ptr = slab.ctypes.data
    put = jax.device_put(slab, dev)
    put.block_until_ready()
    try:
        back_ptr = put.unsafe_buffer_pointer()
    except Exception:  # noqa: BLE001
        back_ptr = None
    out["attempts"]["D_adopt_our_slab"] = {
        "ok": True, "our_ptr": hex(slab_ptr),
        "device_ptr": hex(back_ptr) if back_ptr is not None else None,
        "adopted": back_ptr == slab_ptr}
except Exception as e:  # noqa: BLE001
    out["attempts"]["D_adopt_our_slab"] = {
        "ok": False, "error": f"{type(e).__name__}: {e}"}

# E. pointer identity through the IOBuf seam (only if A gave a pointer the
# HOST can dereference without faulting — guarded by a mem probe through
# /proc/self/mem so a device-address read cannot segfault the child).
a = out["attempts"]["A_unsafe_buffer_pointer"]
if a.get("ok"):
    ptr = int(a["ptr"], 16)
    readable = False
    try:
        with open("/proc/self/mem", "rb") as m:
            m.seek(ptr)
            first = m.read(16)
            readable = len(first) == 16
    except Exception:  # noqa: BLE001
        readable = False
    ident = None
    if readable:
        buf = (ctypes.c_ubyte * 4096).from_address(ptr)
        ident = bytes(buf[:64]) == bytes(np.asarray(arr).reshape(-1)[:64])
    out["attempts"]["E_pointer_identity"] = {
        "ok": True, "host_readable": readable, "bytes_match": ident}

print(json.dumps(out))
"""


def run_child(cpu: bool, timeout: int = 180):
    env = dict(os.environ)
    if cpu:
        env["PROBE_CPU"] = "1"
    proc = subprocess.Popen(
        [sys.executable, "-c", CHILD], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
        tail = stderr.decode(errors="replace")[-2000:]
        for line in stdout.decode(errors="replace").splitlines()[::-1]:
            if line.startswith("{"):
                return json.loads(line), tail
        return {"error": "no json", "stderr": tail}, tail
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except OSError:
            pass
        proc.wait()
        return {"error": f"timeout after {timeout}s"}, ""


def main():
    cpu_only = "--cpu" in sys.argv
    results = {}
    results["cpu_control"] = run_child(cpu=True)[0]
    if not cpu_only:
        results["tpu"] = run_child(cpu=False)[0]
    print(json.dumps(results, indent=2))

    def verdict(r):
        a = r.get("attempts", {})
        ident = a.get("E_pointer_identity", {})
        return (f"`unsafe_buffer_pointer` "
                f"{'returns a pointer' if a.get('A_unsafe_buffer_pointer', {}).get('ok') else 'fails'}"
                f" (host-readable: {ident.get('host_readable')}, "
                f"addresses the payload: {ident.get('bytes_match')}); "
                f"DLPack export "
                f"{'works' if a.get('B_dlpack', {}).get('ok') else 'fails'}; "
                f"`device_put` adopts our slab: "
                f"{a.get('D_adopt_our_slab', {}).get('adopted')}")

    md = ["# PJRT device-memory registration probe — committed output",
          "",
          "Generated by `python tools/pjrt_probe.py` (run it on the chip "
          "to reproduce; each backend below names itself).  Question: can "
          "the ICI transport's `ici_set_slab_registrar` seam be bound to "
          "real device memory or PJRT-pinned staging, the way "
          "rdma/block_pool.cpp registers NIC memory?",
          ""]
    md += [f"- **{name}** ({r.get('backend')}): {verdict(r)}"
           for name, r in results.items()]
    md += ["",
           "```json",
           json.dumps(results, indent=2),
           "```",
           ""]
    with open(os.path.join(os.path.dirname(__file__), "PJRT_PROBE.md"),
              "w") as f:
        f.write("\n".join(md))


if __name__ == "__main__":
    main()
