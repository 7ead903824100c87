"""The main path's device programs compile for the real chip at the real
width, without the chip: `echo_fused` at 64 MB on one described v5e chip,
and `make_nton_exchange` at 64 MB per chip on the described 2x2.  Costs no
chip time and guards every later PR.

The topology is described inside a module fixture, never at import: only
one process may load the TPU's library, and every xdist worker imports
this file (on-chip-measurement §2).  Keep these tests in this one file.
"""

import functools
import json
import os
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent


def _traffic(name: str) -> dict:
    return json.loads(
        (ROOT / "benchmark" / "traffic" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_echo_fused_compiles_at_64mb_for_one_v5e_chip(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from brpc_tpu.ops.echo_kernel import echo_fused

    size = _traffic("tensor64M")["payload_bytes"]
    assert size == 64 << 20
    x = jax.ShapeDtypeStruct((size // 4,), jnp.uint32,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    compiled = jax.jit(
        functools.partial(echo_fused, interpret=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # Request in, response out, and nothing of their size besides.
    assert compiled.memory_analysis().temp_size_in_bytes < size


def test_nton_exchange_compiles_at_64mb_per_chip_for_the_2x2(topo):
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models.echo import make_nton_exchange
    from brpc_tpu.parallel.fabric import Fabric

    per_chip = _traffic("exchange64M")["bytes_per_chip"]
    assert per_chip == 64 << 20
    n = len(topo.devices)
    assert n == 4
    ring = Fabric.auto((n,), ("link",), devices=topo.devices)
    rows = jax.ShapeDtypeStruct((n * n, per_chip // 4 // n), jnp.uint32,
                                sharding=ring.sharding("link"))
    compiled = make_nton_exchange(ring, "link").lower(rows).compile()
    assert "all-to-all" in compiled.as_text()
    analysis = compiled.memory_analysis()
    assert analysis.argument_size_in_bytes == per_chip
    assert analysis.output_size_in_bytes >= per_chip
