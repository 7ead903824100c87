"""Driver `stream_echo`: chunks that start in HBM go down one long-lived,
ordered, credit-windowed stream to a native echo, come back on the same
stream and end in HBM, compared there with what was sent.

One process holds the chip, the Server (`register_native_stream_echo`: no
Python on the server side) and the cell's one Channel; the stream is
opened once, in set-up, with `open_stream`, and every chunk goes through
`Stream.write` and comes back through `Stream.read_block`.  A closed loop
on one client thread keeps `chunks_open` chunks written and not yet read
back, with no pacing: write while fewer are open, else read one, bring it
onto the device, compare.  The next chunk's device-to-host transfer is
started (`zerocopy.host_view`) before the oldest echo is read, so it
crosses beside the read and the H2D; it is waited for and written after
them.

`chunks_open` times the chunk is more than one window, so the credit gate
works in every cycle, and less than the two windows together: one thread
that writes and reads the same stream parks in its write against its own
unread echoes as soon as both windows are full (the reference says the
same of its two FIFOs).

The timed call is one chunk, from the start of its D2H to the end of the
`block_until_ready` on its echo's H2D.  Every chunk is made on the device
from the one before by the reference's rule (reference_stream.next_chunk:
the checksum, made odd, added to every word; the step is the device
plane's `echo_fused`), has never been fetched (`SendOnce`), and is
compared with its echo on the device, one dispatch a chunk, which also
folds the echo's checksum into a running checksum in the order read.
After the drain that running checksum must equal the reference's for the
seed and the number of chunks, and the most bytes that lay unread at
either end (the program's `stream_unread_high_water_bytes`) must be under
the reference's bound, window + one chunk.  `failed` counts a chunk that
mismatched (which an echo out of order does, since no two chunks share a
word), one missing at the drain, a write or a read the stream refused,
and one each for a running checksum or a high-water mark out of bounds.
"""

from __future__ import annotations

import collections
import functools
import shutil
import time

from benchmark import counters
from benchmark.evidence import Evidence
from benchmark.payload import SendOnce, seeded_bits
from benchmark.reference import echo_reference
from benchmark.reference_stream import (MIX, running_checksum_after,
                                        unread_bound)

SHM_FREE_NEEDED = 2 << 30     # served_echo's: the ring's files are sparse
METHOD = "Echo.Stream"        # served by the native stream echo
OPEN_TIMEOUT_MS = 60000


def run(ctx) -> Evidence:
    from brpc_tpu.rpc import Server

    if not hasattr(Server, "register_native_stream_echo"):
        raise SystemExit(
            "this program has no native stream echo "
            "(Server.register_native_stream_echo): the cell cannot run")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.ops.echo_kernel import echo_fused
    from brpc_tpu.rpc import (Channel, RpcError, StreamClosedError,
                              StreamTimeoutError, _lib, open_stream,
                              zerocopy)

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    size = int(mix["chunk_bytes"])
    depth = int(mix["chunks_open"])
    window = int(mix["window_bytes"])
    warm_chunks = int(mix["warm_chunks"])
    read_timeout_ms = int(mix["read_timeout_ms"])
    words = size // 4
    device = ctx.devices[0]
    spans = ctx.spans
    now = time.perf_counter

    shm_free = shutil.disk_usage("/dev/shm").free
    if shm_free < SHM_FREE_NEEDED:
        raise SystemExit(
            f"/dev/shm has {shm_free} bytes free; the transports need "
            f"{SHM_FREE_NEEDED} to be safe from SIGBUS")
    built = _lib.ensure_built()
    _lib.load_library()

    # ---- the cell's own programs, and no others ------------------------
    step = functools.partial(echo_fused, interpret=ctx.interpret)

    def bm_strm_produce(prev):
        response, checksum = step(prev)
        return response + (checksum | jnp.uint32(1))

    def bm_strm_verify(bad, running, back, request):
        bad = bad + jnp.any(
            back != echo_reference(request)).astype(jnp.uint32)
        running = running * jnp.uint32(MIX) + jnp.sum(
            back, dtype=jnp.uint32)
        return bad, running

    produce = jax.jit(bm_strm_produce)
    verify = jax.jit(bm_strm_verify)
    with jax.default_device(device):
        last = seeded_bits(ctx.seed, (words,))
        seed_checksum = jnp.sum(last, dtype=jnp.uint32)
        bad, running = jnp.uint32(0), jnp.uint32(0)
    jax.block_until_ready((last, seed_checksum))

    # The program's mark is the most any stream of the process has held
    # since its start: 0 here in a run of the benchmark; a process that
    # ran other streams before (tier-1's) can only say whether this one
    # raised it.
    unread_before = int(counters.read_native().get(
        "stream_unread_high_water_bytes", 0))
    srv = Server()
    srv.register_native_stream_echo(METHOD)
    srv.start(0)
    ch = stream = None
    try:
        ch = Channel(f"127.0.0.1:{srv.port}", timeout_ms=OPEN_TIMEOUT_MS,
                     **cfg["channel"])
        stream, _ = open_stream(ch, METHOD, timeout_ms=OPEN_TIMEOUT_MS,
                                window_bytes=window)

        guard = SendOnce()
        open_chunks: collections.deque = collections.deque()
        finished: list[tuple[float, float]] = []   # (end, seconds)
        produced = read_back = 0

        def start_next():
            """A fresh chunk, its D2H asked for: (request, view, t0)."""
            nonlocal last, produced
            with spans.span("produce"):
                last = jax.block_until_ready(produce(last))
            produced += 1
            guard.claim(last)
            t0 = now()
            with spans.span("d2h"):
                view, _owner = zerocopy.host_view(last)
            return last, view, t0

        def write(request, view, t0) -> None:
            with spans.span("d2h_wait"):
                if isinstance(view, zerocopy.PendingView):
                    view.resolve()
            with spans.span("write"):
                stream.write(view)
            open_chunks.append((request, t0))

        def read_one() -> None:
            nonlocal bad, running, read_back
            request, t0 = open_chunks.popleft()
            with spans.span("wait"):
                stream.next_len(read_timeout_ms)
            with spans.span("read"):
                block = stream.read_block(timeout_ms=0)
            with spans.span("h2d"):
                back = jax.block_until_ready(jax.device_put(
                    block.view(np.uint32), device))
            t1 = now()
            read_back += 1
            finished.append((t1, t1 - t0))
            with spans.span("verify"):
                bad, running = verify(bad, running, back, request)

        def cycle() -> None:
            """Write while fewer than `depth` are open, else read one."""
            if len(open_chunks) < depth:
                write(*start_next())
            else:
                fresh = start_next()
                read_one()
                write(*fresh)

        # ---- one untimed window, then the timed one without a pause ----
        while len(finished) < warm_chunks:
            cycle()
        before = counters.read_native()
        compiles_before = ctx.compiles.count
        t_open = now()
        deadline = t_open + ctx.seconds
        trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
        traced_from = None
        missing = refused = 0
        while True:
            try:
                cycle()
            except RpcError as e:
                # The stream refused a write or a read (closed, or no
                # echo in `read_timeout_ms`): the run is over, and failed.
                print(f"# the stream failed: {e!r}", flush=True)
                refused = 1
                t_close = now()
                break
            t = now()
            if t >= deadline:
                t_close = t
                break
            if ctx.trace and traced_from is None and t >= trace_at:
                ctx.start_trace()
                traced_from = now()
        compiles_in_window = ctx.compiles.count - compiles_before
        after = counters.read_native()
        traced = None
        if traced_from is not None:
            ctx.stop_trace()
            traced = (traced_from, t_close)
        while open_chunks and not refused:
            try:
                read_one()
            except (StreamTimeoutError, StreamClosedError) as e:
                print(f"# missing at the drain: {e!r}", flush=True)
                missing = len(open_chunks) + 1
                break
        mismatched = int(bad)
        running_checksum = int(running)
        unread_client = stream.unread_high_water
        unread_any = int(after.get("stream_unread_high_water_bytes", 0))
        transport = ch.transport
    finally:
        if stream is not None:
            stream.destroy()
        if ch is not None:
            ch.close()
        srv.stop()

    bound = unread_bound(window, size)
    running_expected = running_checksum_after(
        int(seed_checksum), words, read_back)
    within_bound = unread_client <= bound and (
        unread_any <= bound or unread_any == unread_before)
    counted = [(end, s) for end, s in finished if t_open < end <= t_close]
    failed = (mismatched + missing + refused + int(not within_bound)
              + int(running_checksum != running_expected))
    attempted = (sum(1 for end, _ in finished if end > t_open)
                 + missing + refused)
    busy = sum(spans.total(n, t_open, t_close) for n in
               ("produce", "d2h", "d2h_wait", "write", "read", "h2d",
                "verify"))
    yardstick = sum(spans.total(n, t_open, t_close)
                    for n in ("produce", "verify"))
    return Evidence(
        t_open=t_open, t_close=t_close,
        call_s=[s for _, s in counted], call_end=[end for end, _ in counted],
        bytes_per_call=size, attempted=attempted, failed=failed,
        correct=(failed == 0 and transport == cfg["transport"]),
        compiles_in_window=compiles_in_window, spans=spans,
        counters=counters.delta(before, after),
        traced=traced,
        notes={
            "transport": transport,
            "transport_expected": cfg["transport"],
            "running_checksum": running_checksum,
            "running_checksum_expected": running_expected,
            "unread_within_bound": within_bound,
            "unread_within_bound_expected": True,
            "unread_high_water_bytes": unread_any,
            "unread_high_water_client_bytes": unread_client,
            "unread_bound_bytes": bound,
            "window_bytes": window,
            "chunk_bytes": size,
            "chunks_open": depth,
            "chunks_produced": produced,
            "chunks_read_back": read_back,
            "chunks_mismatched_on_device": mismatched,
            "chunks_missing_at_drain": missing,
            "stream_refusals": refused,
            "native_build": built,
            "seed_checksum": int(seed_checksum),
            "device_step": "echo_fused",
            "client_thread_busy_share": busy / (t_close - t_open),
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
