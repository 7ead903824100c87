"""The pool programs of `kv_prefix_linear` compile for the real chip at
the timed sizes, without the chip: the produce and state programs on
pools of 4,608 pages of Kimi-Linear's 7 MLA layers and 16 state slots of
its 20 KDA layers, each pool donated where it is written, on a window of
64 pages and on a turn's 4, and `read_page` / `write_page` on the state
pool.  Costs no chip time and guards every later PR.

As `test_real_width_compile_kv_prefix.py`: the topology is described
inside a module fixture, never at import.
"""

import json
import os
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
HBM_BYTES = 16 << 30
PAGE = 1032192
STATE = 43417600


def _sizes() -> dict:
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "agent4_128k_replay.json").read_text())
    cfg = json.loads((ROOT / "benchmark" / "configs"
                      / "kv_prefix_linear.json").read_text())
    page = (mix["page_layers"], mix["page_tokens"],
            cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    state = (mix["snapshot_layers"], 8480, 128)
    return {"page": page, "pool": (mix["pool_pages"],) + page,
            "state": state, "states": (mix["state_slots"],) + state,
            "window": mix["fetch_window_pages"],
            "turn": mix["turn_pages"]}


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


def _programs(sharding, pages: int):
    import jax.numpy as jnp

    from brpc_tpu.models import kv_pool

    sizes = _sizes()
    return {
        "pool": _shape(sizes["pool"], kv_pool.DTYPE, sharding),
        "states": _shape(sizes["states"], kv_pool.DTYPE, sharding),
        "slots": _shape((pages,), jnp.int32, sharding),
        "slot": _shape((), jnp.int32, sharding),
        "page": _shape(sizes["page"], kv_pool.DTYPE, sharding),
        "state": _shape(sizes["state"], kv_pool.DTYPE, sharding),
        "consts": _shape((pages,), jnp.uint32, sharding),
    }


@pytest.mark.parametrize("pages", [64, 4])
def test_the_produce_and_state_programs_write_in_place_and_fit(
        one_chip, pages):
    import jax
    import jax.numpy as jnp

    from benchmark import reference_kv, reference_kv_prefix
    from brpc_tpu.models import kv_pool

    sizes = _sizes()
    assert (sizes["window"], sizes["turn"]) == (64, 4)
    a = _programs(one_chip, pages)
    pool_bytes, states_bytes = 4608 * PAGE, 16 * STATE
    assert (pool_bytes, states_bytes) == (4756340736, 694681600)

    def bm_kvl_produce(producing, admitting, from_slots, to_slots, base,
                       consts):
        run = reference_kv_prefix.next_pages(base, consts)
        return (kv_pool.kv_write_pages(producing, from_slots, run),
                kv_pool.kv_write_pages(admitting, to_slots, run))

    def bm_kvl_state_produce(producing_states, admitting_states, out_slot,
                             session_slot, admitting, page_slots):
        sums = jax.vmap(reference_kv.page_checksum)(
            kv_pool.kv_read_pages(admitting, page_slots))
        add = jnp.sum(sums | jnp.uint32(1), dtype=jnp.uint32)
        state = reference_kv_prefix.next_pages(
            kv_pool.kv_read_page(admitting_states, session_slot),
            add[None])[0]
        return (kv_pool.kv_write_page(producing_states, out_slot, state),
                kv_pool.kv_write_page(admitting_states, session_slot, state))

    got = jax.jit(bm_kvl_produce, donate_argnums=(0, 1)).lower(
        a["pool"], a["pool"], a["slots"], a["slots"], a["page"],
        a["consts"]).compile().memory_analysis()
    assert got.alias_size_in_bytes == 2 * pool_bytes
    assert got.temp_size_in_bytes < 4 * pages * PAGE + (1 << 20)
    got = jax.jit(bm_kvl_state_produce, donate_argnums=(0, 1)).lower(
        a["states"], a["states"], a["slot"], a["slot"], a["pool"],
        a["slots"]).compile().memory_analysis()
    assert got.alias_size_in_bytes == 2 * states_bytes
    # The new pages read once, the state and its 32-bit words.
    assert got.temp_size_in_bytes < pages * PAGE + 4 * STATE
    # The four pools and what a turn holds beside them: a landed window
    # and two read back (each also as the flat words it crosses as), the
    # base page and state, the landed state, the new state read back
    # twice over: under the chip with 2 GiB to spare.
    held = (2 * pool_bytes + 2 * states_bytes + 5 * 64 * PAGE + PAGE
            + 4 * STATE)
    assert held + got.temp_size_in_bytes < HBM_BYTES - (2 << 30)


def test_the_state_pool_reads_and_writes_a_slot_in_place(one_chip):
    import jax

    from brpc_tpu.models import kv_pool

    a = _programs(one_chip, 1)
    read = jax.jit(kv_pool.kv_read_page).lower(
        a["states"], a["slot"]).compile().memory_analysis()
    assert read.output_size_in_bytes == STATE       # no padding
    write = jax.jit(kv_pool.kv_write_page, donate_argnums=0).lower(
        a["states"], a["slot"], a["state"]).compile().memory_analysis()
    assert write.alias_size_in_bytes == 16 * STATE
    assert write.temp_size_in_bytes < 1 << 20
