"""``chip_smoke.py`` off the chip: it refuses to pass without a TPU, and its
phases pass on the CPU backend at a small size (the 4 emulated devices of
the conftest stand in for a 2x2 host)."""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke as cs
from repro.workloads import generators as gen


def test_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, cs.__file__], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}}


@pytest.fixture
def smoke():
    sm = cs.Smoke("(not used)")
    yield sm
    jax.monitoring.unregister_event_duration_listener(sm._on_duration)
    jax.monitoring.unregister_event_listener(sm._on_event)


def test_one_chip_phases_pass_small(smoke, tmp_path, capsys):
    stream = gen.mixed_stream(6, 0, sizes=(6, 8))
    cs.one_chip(smoke, str(tmp_path), stream, [],
                [gen.musicbrainz_query(24, seed=256)], 2)
    out = capsys.readouterr().out
    assert smoke.failed == [], out
    for name in ("serve-cold", "serve-warm", "serve-solo", "reference",
                 "heuristic", "pallas"):
        assert f"[{name}] PASS" in out


def test_four_device_phases_pass_small(smoke, tmp_path, capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs the conftest's 4 emulated devices")
    cs.four_chips(smoke, str(tmp_path), gen.mixed_stream(8, 0, sizes=(6, 8)),
                  [gen.musicbrainz_query(17, seed=417)], 4)
    out = capsys.readouterr().out
    assert smoke.failed == [], out
    assert "[lattice-4] PASS" in out
