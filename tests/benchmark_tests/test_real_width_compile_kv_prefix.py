"""The pool programs of `kv_prefix` compile for the real chip at the
timed sizes, without the chip: `read_pages` / `write_pages` on a pool of
640 pages of 61 MLA layers, on a window of 16 pages and on the smallest
piece, each pool donated where it is written (a copy of either does not
fit beside the two), and the yardstick's produce program, which writes
both.  Costs no chip time and guards every later PR.

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
BLOCK = 8994816


def _sizes() -> dict:
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "sessions6_zipf.json").read_text())
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "kv_prefix.json").read_text())
    page = (mix["page_layers"], mix["page_tokens"],
            cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return {"page": page, "pool": (mix["pool_pages"],) + page,
            "window": mix["fetch_window_pages"]}


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


@pytest.mark.parametrize("pages", [16, 4, 1])
def test_the_pools_programs_compile_at_640_pages(one_chip, pages):
    import jax
    import jax.numpy as jnp

    from brpc_tpu.models import kv_pool

    sizes = _sizes()
    assert sizes["window"] == 16
    pool = _shape(sizes["pool"], kv_pool.DTYPE, one_chip)
    slots = _shape((pages,), jnp.int32, one_chip)
    run = _shape((pages,) + sizes["page"], kv_pool.DTYPE, one_chip)
    pool_bytes = 640 * BLOCK
    assert pool_bytes == 5756682240
    read = jax.jit(kv_pool.kv_read_pages).lower(pool, slots).compile()
    got = read.memory_analysis()
    assert got.output_size_in_bytes == pages * BLOCK     # no padding
    assert got.temp_size_in_bytes < 1 << 20
    write = jax.jit(kv_pool.kv_write_pages, donate_argnums=0).lower(
        pool, slots, run).compile()
    got = write.memory_analysis()
    # The pool is written where it lies: nothing of its size besides.
    assert got.alias_size_in_bytes == pool_bytes == got.output_size_in_bytes
    assert got.temp_size_in_bytes < 1 << 20


def test_the_produce_program_writes_both_pools_in_place_and_all_fits(
        one_chip):
    import jax
    import jax.numpy as jnp

    from benchmark import reference_kv_prefix
    from brpc_tpu.models import kv_pool

    sizes = _sizes()
    window = sizes["window"]
    pool = _shape(sizes["pool"], kv_pool.DTYPE, one_chip)
    slots = _shape((window,), jnp.int32, one_chip)
    base = _shape(sizes["page"], kv_pool.DTYPE, one_chip)
    consts = _shape((window,), jnp.uint32, one_chip)

    def bm_kvp_produce(producing, admitting, from_slots, to_slots, base,
                       consts):
        pages = reference_kv_prefix.next_pages(base, consts)
        return (kv_pool.kv_write_pages(producing, from_slots, pages),
                kv_pool.kv_write_pages(admitting, to_slots, pages))

    got = jax.jit(bm_kvp_produce, donate_argnums=(0, 1)).lower(
        pool, pool, slots, slots, base, consts).compile().memory_analysis()
    pool_bytes = 640 * BLOCK
    assert got.alias_size_in_bytes == 2 * pool_bytes
    # The window's pages, their 32-bit words and little else.
    assert got.temp_size_in_bytes < 4 * window * BLOCK
    # Both pools and what a turn holds on the device beside them: two
    # windows read back and on their way to the host (each also as the
    # flat words it crosses as), one landed window, the base page.
    held = 2 * pool_bytes + (2 * 2 + 1) * window * BLOCK + BLOCK
    assert 2 * pool_bytes < held + got.temp_size_in_bytes < HBM_BYTES - (
        2 << 30)
