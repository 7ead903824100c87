"""Driver `served_echo`: a payload that starts in HBM is served by the RPC
stack and lands in HBM again, compared there with what was sent.

One process holds the chip, the Server and the cell's one Channel (client
and server share the host's loopback; the configuration file says so).  A
closed loop keeps `calls_in_flight` calls open from one client thread:
poll k completions (at most `POLL_MAX`), bring their responses onto the
device, produce k new requests on the device, fetch them, submit them in
one crossing.  `POLL_MAX` well under a deep window keeps the loop a
steady round-robin; a poll that takes whatever is ready lets the batches
grow and shrink, and the median call time with them (PERF.md, PR 22).
One thread, a closed loop, the native echo handler and the tstd protocol
are what this driver is; a mix or a configuration cannot ask for another.

The timed call is `zerocopy.host_view(request)` -> `pipeline.submit` ->
`pipeline.poll` -> `jax.device_put(response)` + `block_until_ready`, from
the start of the D2H to the end of the H2D.  Staging is reached only
through the program's own functions, so a change inside them shows.

Every request is a device array that has never been fetched (`SendOnce`
refuses any other): the device plane's echo step makes it from the one
before, and the step's own checksum, made odd, is added to every element,
so that the checksum cannot be optimised away and no word of a request
equals the word at the same place in the one before.  Two requests
further apart differ in every word too, unless the odd numbers added
between them sum to 0 mod 2^32 (a chance of 2^-32 a pair).  So whatever a
response buffer still holds from an earlier call, and whatever belongs
to another call in flight, differs from this call's request in every
word: a chunk the transport never wrote, or wrote from the wrong call,
fails the compare, and a recycled buffer needs no poisoning (the
rehearsal runs both faults).  One argument and one result: on the v5e host a
program's launch costs some 0.1 ms per buffer it allocates (PERF.md),
and this program is the yardstick's, not the caller's.  The compare runs
on the device, a fixed group of responses per dispatch, folded into one
device scalar that is fetched once, after the window.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import shutil
import time

from benchmark import counters
from benchmark.evidence import Evidence
from benchmark.payload import SendOnce, seeded_bits
from benchmark.reference import echo_reference

# The shm ring and each shm connection's two 256 MB one-sided windows are
# shm_open+ftruncate files with no fallocate: on a tmpfs too small to
# back a touched page that is a SIGBUS, not an error (chip_smoke.py).
SHM_FREE_NEEDED = 2 << 30
METHOD = "Echo.Echo"          # served by the native echo handler
CALL_TIMEOUT_MS = 60000       # no 64 MB call under a full window is cut
FUSED_FROM_BYTES = 1 << 20    # bench.py's rule: echo_fused from 1 MB
POLL_MAX = 8


@dataclasses.dataclass
class _Call:
    request: object
    buf: object
    t0: float
    t_submit: float = 0.0


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from brpc_tpu.models.echo import single_chip_echo_step
    from brpc_tpu.ops.echo_kernel import echo_fused
    from brpc_tpu.rpc import Channel, Server, _lib, zerocopy

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    size = int(mix["payload_bytes"])
    depth = int(mix["calls_in_flight"])
    group = int(mix["verify_group"])
    warm_calls = int(mix["warm_calls"])
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
    if size >= FUSED_FROM_BYTES:
        step_name = "echo_fused"
        step = functools.partial(echo_fused, interpret=ctx.interpret)
    else:
        step_name = "single_chip_echo_step"
        step = single_chip_echo_step

    def bm_produce(prev):
        response, checksum = step(prev)
        return response + (checksum | jnp.uint32(1))

    def bm_verify(bad, backs, requests):
        for back, request in zip(backs, requests):
            bad = bad + jnp.any(
                back != echo_reference(request)).astype(jnp.uint32)
        return bad

    produce = jax.jit(bm_produce)
    verify = jax.jit(bm_verify)
    with jax.default_device(device):
        last = seeded_bits(ctx.seed, (size // 4,))
        seed_checksum = jnp.sum(last, dtype=jnp.uint32)
        bad = jnp.uint32(0)
        # The drain compares what is left one response at a time.
        bad = verify(bad, (last,), (last,))
    jax.block_until_ready((last, bad))

    srv = Server()
    srv.register_native_echo(METHOD)
    srv.start(0)
    ch = pipe = None
    try:
        ch = Channel(f"127.0.0.1:{srv.port}",
                     timeout_ms=CALL_TIMEOUT_MS,
                     **cfg["channel"])
        pipe = ch.pipeline()

        guard = SendOnce()
        # A response buffer is used again only after the compare that
        # read its bytes has finished on the device: where a backend
        # adopts host memory instead of copying it, the array made from
        # the buffer is the buffer.
        free = collections.deque(
            np.zeros(size, dtype=np.uint8)
            for _ in range(depth + 2 * group + 2))
        cooling: collections.deque = collections.deque()
        unverified: list[tuple] = []
        inflight: dict[int, _Call] = {}
        finished: list[tuple[float, float]] = []   # (end, seconds)
        not_ok_at: list[float] = []
        produced = 0

        def take_buffer():
            while cooling and (not free or cooling[0][0].is_ready()):
                folded, bufs = cooling.popleft()
                jax.block_until_ready(folded)
                free.extend(bufs)
            return free.popleft()

        def flush(n: int) -> None:
            nonlocal bad
            part = unverified[:n]
            del unverified[:n]
            with spans.span("verify"):
                bad = verify(bad, tuple(p[0] for p in part),
                             tuple(p[1] for p in part))
            cooling.append((bad, [p[2] for p in part]))

        def refill(k: int) -> None:
            nonlocal last, produced
            requests = []
            with spans.span("produce"):
                for _ in range(k):
                    last = produce(last)
                    requests.append(last)
                jax.block_until_ready(last)
            produced += k
            calls, flats = [], []
            for request in requests:
                guard.claim(request)
                t0 = now()
                with spans.span("d2h"):
                    flat, _owner = zerocopy.host_view(request)
                flats.append(flat)
                calls.append(_Call(request, take_buffer(), t0))
            with spans.span("submit"):
                tokens = pipe.submit(METHOD, flats,
                                     resp_bufs=[c.buf for c in calls])
            t_submit = now()
            for token, call in zip(tokens, calls):
                call.t_submit = t_submit
                inflight[token] = call

        def poll() -> list:
            with spans.span("poll"):
                done = pipe.poll(max_n=POLL_MAX, timeout_ms=0)
            if not done:
                with spans.span("wait"):
                    done = pipe.poll(max_n=POLL_MAX,
                                     timeout_ms=CALL_TIMEOUT_MS)
                if not done:
                    raise TimeoutError(
                        f"no completion in {CALL_TIMEOUT_MS} ms with "
                        f"{len(inflight)} calls in flight")
            return done

        def finish(done) -> None:
            t_polled = now()
            for c in done:
                call = inflight.pop(c.token)
                spans.add("wire", call.t_submit, t_polled)
                if not c.ok or c.resp_len != size:
                    print(f"# call failed: {c!r}", flush=True)
                    not_ok_at.append(t_polled)
                    free.append(call.buf)
                    continue
                with spans.span("h2d"):
                    back = jax.block_until_ready(jax.device_put(
                        call.buf.view(np.uint32), device))
                t1 = now()
                finished.append((t1, t1 - call.t0))
                unverified.append((back, call.request, call.buf))
                if len(unverified) >= group:
                    flush(group)

        # ---- one untimed window, then the timed one without a pause ----
        refill(depth)
        while len(finished) < warm_calls:
            done = poll()
            finish(done)
            refill(len(done))
        before = counters.read_native()
        compiles_before = ctx.compiles.count
        t_open = now()
        deadline = t_open + ctx.seconds
        trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
        traced_from = None
        while True:
            done = poll()
            finish(done)
            t = now()
            if t >= deadline:
                t_close = t
                break
            if ctx.trace and traced_from is None and t >= trace_at:
                ctx.start_trace()
                traced_from = now()
            refill(len(done))
        compiles_in_window = ctx.compiles.count - compiles_before
        after = counters.read_native()
        traced = None
        if traced_from is not None:
            ctx.stop_trace()
            traced = (traced_from, t_close)
        while inflight:
            finish(poll())
        while unverified:
            flush(1)
        mismatched = int(bad)
        transport = ch.transport
    finally:
        if pipe is not None:
            pipe.close()
        if ch is not None:
            ch.close()
        srv.stop()

    counted = [(end, s) for end, s in finished if t_open < end <= t_close]
    not_ok = sum(1 for t in not_ok_at if t > t_open)
    attempted = sum(1 for end, _ in finished if end > t_open) + not_ok
    failed = not_ok + mismatched
    busy = sum(spans.total(n, t_open, t_close) for n in
               ("produce", "d2h", "submit", "poll", "h2d", "verify"))
    yardstick = sum(spans.total(n, t_open, t_close)
                    for n in ("produce", "verify"))
    return Evidence(
        t_open=t_open, t_close=t_close,
        call_s=[s for _, s in counted], call_end=[end for end, _ in counted],
        bytes_per_call=size, attempted=attempted, failed=failed,
        correct=(failed == 0 and not not_ok_at
                 and transport == cfg["transport"]),
        compiles_in_window=compiles_in_window, spans=spans,
        counters=counters.delta(before, after),
        traced=traced,
        notes={
            "transport": transport,
            "transport_expected": cfg["transport"],
            "native_build": built,
            "seed_checksum": int(seed_checksum),
            "requests_sent_once": produced,
            "device_step": step_name,
            "client_thread_busy_share": busy / (t_close - t_open),
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
