"""``benchmark/metrics/latent_slot_kernel_share.py`` (ISSUE 43): the
reader of the trace-time gauge ``attention/latent_slot_kernel_share``
over a registry with and without it, and its ``BENCHMARK.json`` entry.
A file of its own: ``benchmark/tests/``' files are the accepted
benchmark's."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import manifest  # noqa: E402
from scalable_agent_tpu.obs import registry  # noqa: E402

NAME = "latent_slot_kernel_share"
GAUGE = "attention/latent_slot_kernel_share"


@pytest.fixture
def reader():
    return manifest.load_module(
        os.path.join(ROOT, "benchmark", "metrics", NAME + ".py"), NAME)


@pytest.mark.parametrize("value", [None, 1.0, 0.8, 0.0],
                         ids=["no gauge", "every write", "four of five",
                              "every write fell back"])
def test_the_reader_gives_the_gauge_or_nothing(reader, monkeypatch, value):
    """A parent without the kernel has no such gauge and prints nothing;
    a program whose every write fell back reads 0.0, which is a reading
    and is printed."""
    fresh = registry.MetricsRegistry()
    fresh.gauge("cache/latent_bytes_per_token").set(1152.0)
    if value is not None:
        fresh.gauge(GAUGE).set(value)
    monkeypatch.setattr(registry, "_registry", fresh)
    assert reader.read(ctx=None) == value


def test_the_reader_reads_what_a_traced_write_set(reader, monkeypatch):
    import jax.numpy as jnp

    from scalable_agent_tpu.ops import attention

    monkeypatch.setattr(registry, "_registry", registry.MetricsRegistry())
    monkeypatch.setattr(attention, "_slot_writes", [0, 0])
    attention.latent_ring_write(jnp.zeros((2, 16, 128), jnp.bfloat16),
                                jnp.ones((2, 1, 16)), jnp.int32(130))
    assert reader.read(ctx=None) == 1.0


def test_the_entry_is_the_caches_and_kanana2s_alone():
    (entry,) = [m for m in manifest.load_benchmark()["per_layer"]
                if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "cache",
        "moves": "fused_env_frames_per_s",
        "workloads": ["kanana2.ingraph"]}
    cell = manifest.load_cell("kanana2.ingraph")
    assert NAME in {m.name for m in cell.per_layer}
    for other in ("trinity.ingraph", "shallow.ingraph"):
        assert NAME not in {
            m.name for m in manifest.load_cell(other).per_layer}
