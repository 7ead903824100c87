"""Test fixture: virtual 8-device CPU mesh (SURVEY.md §4 — the reference
tests distributed behavior with in-process loopback; ours is a forced
multi-device CPU backend).  Set before anything imports jax; the tests
never touch an accelerator, so they may run beside a process that holds
the chip."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: the sanitizer matrix and long soaks, excluded from the "
        "tier-1 gate (run with -m slow)",
    )
    config.addinivalue_line(
        "markers",
        "san: the sanitizer matrix (TSan suite sweep, ASan+LSan full "
        "suite, fuzz-corpus replay) — run with -m san; every test "
        "skips gracefully when the toolchain lacks the sanitizer "
        "runtime.  Tier-1 keeps a bounded TSan smoke (fiber suite) and "
        "tools/lint_trpc.py instead of the whole matrix.",
    )
