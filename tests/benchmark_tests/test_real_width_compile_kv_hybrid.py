"""The two pools' programs of `kv_hybrid` compile for the real chip at
the timed sizes, without the chip: `read_pages` / `write_pages` on a
page pool of 3,072 pages of 7 MLA layers, `read_page` / `write_page` on
a state pool of 64 slots of 20 KDA layers, each pool donated where it is
written (a copy of either does not fit beside the four).  Costs no chip
time and guards every later PR.

As `test_real_width_compile.py`, whose file no later PR may edit: the
topology is described inside a module fixture, never at import, since
only one process may load the TPU's library and every xdist worker
imports this file (on-chip-measurement §2).
"""

import json
import os
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
HBM_BYTES = 16 << 30


def _sizes() -> dict:
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "handover1k_d2.json").read_text())
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "kv_hybrid.json").read_text())
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return {
        "page_pool": (mix["pool_pages"], len(cfg["full_attn_layers"]),
                      mix["page_tokens"], width),
        "state_pool": (mix["state_slots"], len(cfg["kda_layers"]),
                       cfg["snapshot_record_bytes"] // 256, 128),
        "pages": mix["prompt_tokens"] // mix["page_tokens"],
        "in_flight": mix["sequences_in_flight"],
        "sequence_bytes": mix["bytes_per_call"],
    }


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

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
    yield SingleDeviceSharding(described.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_the_page_pools_programs_compile_at_3072_pages(one_chip):
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models import kv_pool

    sizes = _sizes()
    pool = _shape(sizes["page_pool"], kv_pool.DTYPE, one_chip)
    slots = _shape((sizes["pages"],), jnp.int32, one_chip)
    pages = _shape((sizes["pages"],) + sizes["page_pool"][1:],
                   kv_pool.DTYPE, one_chip)
    pool_bytes = 2
    for n in sizes["page_pool"]:
        pool_bytes *= n
    assert pool_bytes == 3170893824
    read = jax.jit(kv_pool.kv_read_pages).lower(pool, slots).compile()
    got = read.memory_analysis()
    assert got.output_size_in_bytes == 8 * 7 * 147456    # no padding
    assert got.temp_size_in_bytes < 1 << 20
    write = jax.jit(kv_pool.kv_write_pages, donate_argnums=0).lower(
        pool, slots, pages).compile()
    got = write.memory_analysis()
    # The pool is written where it lies: nothing of its size besides.
    assert got.alias_size_in_bytes == pool_bytes == got.output_size_in_bytes
    assert got.temp_size_in_bytes < 1 << 20


def test_the_state_pools_programs_compile_at_64_slots_and_all_four_fit(
        one_chip):
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models import kv_pool

    sizes = _sizes()
    pool = _shape(sizes["state_pool"], kv_pool.DTYPE, one_chip)
    slot = _shape((), jnp.int32, one_chip)
    states = _shape(sizes["state_pool"][1:], kv_pool.DTYPE, one_chip)
    pool_bytes = 2
    for n in sizes["state_pool"]:
        pool_bytes *= n
    assert pool_bytes == 2778726400
    read = jax.jit(kv_pool.kv_read_page).lower(pool, slot).compile()
    got = read.memory_analysis()
    assert got.output_size_in_bytes == 20 * 2170880      # no padding
    assert got.temp_size_in_bytes < 1 << 20
    write = jax.jit(kv_pool.kv_write_page, donate_argnums=0).lower(
        pool, slot, states).compile()
    got = write.memory_analysis()
    assert got.alias_size_in_bytes == pool_bytes == got.output_size_in_bytes
    assert got.temp_size_in_bytes < 1 << 20
    # Two ranks' pools and, a sequence in flight, what the driver holds
    # of it on the device (produced, read back, landed, the one before).
    held = 2 * (3170893824 + pool_bytes) + (
        4 * sizes["in_flight"] + 2) * sizes["sequence_bytes"]
    assert 11899240448 < held < HBM_BYTES - (2 << 30)
