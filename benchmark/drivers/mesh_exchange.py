"""Driver `mesh_exchange`: the N-to-N exchange over the chips of one host,
one compiled all-to-all per call, each exchange fed by the one before.

The timed call is one exchange ended by `block_until_ready`.  The check
is on the device, in one dispatch after every `verify_every`-th
exchange: what each peer received against the plain reference's answer
(the exchange is its own inverse, so the answers alternate between the
transposed payload and the payload itself), and the program's per-peer
checksums against the reference's.  An exchange only moves bytes and each
is fed by the one before, so a byte that is wrong once is wrong in every
later exchange and the next check sees it.  Launching a program on four
chips costs the host 0.4 ms here (PERF.md), which is why not every
exchange is followed by one.  The flags are kept per peer and fetched
once, after the window.  No native library is loaded.
"""

from __future__ import annotations

import time

from benchmark import work
from benchmark.evidence import Evidence
from benchmark.payload import seeded_bits
from benchmark.reference import exchange_reference, shard_checksums


def _check_spread(x, devices, shard_shape, what: str) -> None:
    """Every device holds a `shard_shape` piece of `x` (the rule of
    __graft_entry__._check_spread): values alone cannot show that a
    program written on one chip did not put everything on the first."""
    held = {s.device for s in x.addressable_shards}
    if held != set(devices):
        raise AssertionError(
            f"{what}: lives on {sorted(d.id for d in held)}, expected "
            f"all of {sorted(d.id for d in devices)}")
    for s in x.addressable_shards:
        if s.data.shape != tuple(shard_shape):
            raise AssertionError(
                f"{what}: device {s.device.id} holds {s.data.shape}, "
                f"expected {tuple(shard_shape)}")


def run(ctx) -> Evidence:
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.echo import make_nton_exchange
    from brpc_tpu.parallel.fabric import Fabric

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    n = int(cfg["peers"])
    if len(ctx.devices) < n:
        raise SystemExit(
            f"{ctx.cell.config_name} needs {n} chips, the cell was given "
            f"{len(ctx.devices)}")
    devices = ctx.devices[:n]
    per_chip = int(mix["bytes_per_chip"])
    chunk = per_chip // 4 // n
    warm_calls = int(mix["warm_calls"])
    verify_every = int(mix["verify_every"])
    spans = ctx.spans
    now = time.perf_counter

    axis = cfg["axis"]
    ring = Fabric.auto((n,), (axis,), devices=devices)
    rows_on = ring.sharding(axis)
    payload = seeded_bits(ctx.seed, (n * n, chunk), rows_on)
    exchange = make_nton_exchange(ring, axis).lower(payload).compile()

    def bm_reference(rows):
        there = exchange_reference(rows, n)
        return there, shard_checksums(there, n), shard_checksums(rows, n)

    def bm_verify(bad, received, sums, want, want_sums):
        wrong = jnp.any((received != want).reshape(n, -1), axis=1)
        return bad + (wrong | (sums != want_sums)).astype(jnp.uint32)

    there, there_sums, here_sums = jax.jit(
        bm_reference, out_shardings=(rows_on, rows_on, rows_on))(payload)
    verify = jax.jit(bm_verify, out_shardings=rows_on)
    # After an odd number of exchanges the peers hold the transposed
    # payload, after an even number the payload itself.
    want = ((payload, here_sums), (there, there_sums))
    bad = jax.device_put(jnp.zeros((n,), jnp.uint32), rows_on)

    received, sums = exchange(payload)
    _check_spread(received, devices, (n, chunk), "exchange")
    _check_spread(sums, devices, (1,), "exchange checksums")
    bad = verify(bad, received, sums, *want[1])
    _check_spread(bad, devices, (1,), "verify flags")
    done = checked = 1

    def one_exchange():
        nonlocal received, sums, done
        t0 = now()
        with spans.span("exchange"):
            received, sums = exchange(received)
            jax.block_until_ready((received, sums))
        t1 = now()
        done += 1
        if done % verify_every == 0:
            check()
        return t1, t1 - t0

    def check():
        nonlocal bad, checked
        with spans.span("verify"):
            bad = verify(bad, received, sums, *want[done % 2])
        checked += 1

    for _ in range(warm_calls):
        one_exchange()
    jax.block_until_ready(bad)
    compiles_before = ctx.compiles.count
    finished = []
    t_open = now()
    deadline = t_open + ctx.seconds
    trace_at = deadline - min(float(mix["trace_seconds"]), ctx.seconds)
    traced_from = None
    while True:
        finished.append(one_exchange())
        t = finished[-1][0]
        if t >= deadline:
            t_close = t
            break
        if ctx.trace and traced_from is None and t >= trace_at:
            ctx.start_trace()
            traced_from = now()
    compiles_in_window = ctx.compiles.count - compiles_before
    traced = None
    if traced_from is not None:
        ctx.stop_trace()
        traced = (traced_from, t_close)
    check()  # whatever the last exchanges did is in what is held now
    flags = jax.device_get(bad)
    failed = int(flags.max())
    yardstick = spans.total("verify", t_open, t_close)
    return Evidence(
        t_open=t_open, t_close=t_close,
        call_s=[s for _, s in finished],
        call_end=[end for end, _ in finished],
        bytes_per_call=work.exchange_bytes_leaving_chip(per_chip, n),
        attempted=len(finished), failed=failed, correct=failed == 0,
        compiles_in_window=compiles_in_window, spans=spans, counters={},
        traced=traced,
        notes={
            "peers": n,
            "device_coords": [list(getattr(d, "coords", ()))
                              for d in devices],
            "seed_checksum": int(jax.device_get(here_sums).sum(
                dtype="uint32")),
            "exchanges": done, "checks": checked,
            "yardstick_share_of_window": yardstick / (t_close - t_open),
        })
