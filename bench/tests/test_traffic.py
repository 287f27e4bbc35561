"""What the traffic copies draw, pinned by checksum (seed 0, request 0 of
a mix; one file per mix under ``traffic_checksums/``), so that the
yardstick cannot move under a later change."""

import hashlib
import json
import os

import numpy as np
import pytest

from bench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")
PINS = os.path.join(HERE, "traffic_checksums")
PINNED = {f[:-5]: json.load(open(os.path.join(PINS, f)))
          for f in os.listdir(PINS) if f.endswith(".json")}


def digest(d: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(d):
        h.update(k.encode())
        h.update(np.ascontiguousarray(d[k], np.float64).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_mix_draws_what_was_pinned(name):
    mix = json.load(open(os.path.join(MIXES, name + ".json")))
    d = traffic.draw(mix, 0, traffic.WINDOW, 0)
    assert d["arrival"].shape[0] == PINNED[name]["n_tasks"]
    assert digest(d) == PINNED[name]["sha256"]


def test_requests_differ_and_repeat_by_seed():
    mix = {"generator": "offline", "util": 0.2}
    a = traffic.draw(mix, 2**31 + 5, traffic.WINDOW, 0)
    b = traffic.draw(mix, 2**31 + 5, traffic.WINDOW, 1)
    assert digest(a) == digest(traffic.draw(mix, 2**31 + 5, 0, 0))
    assert digest(a) != digest(b)


def test_offline_lands_on_the_target_utilization():
    d = traffic.draw({"generator": "offline", "util": 1.6}, 7, 0, 3)
    assert d["utilization"].sum() == pytest.approx(1.6 * 1024, abs=1e-3)
    assert np.all(d["arrival"] == 0)
    assert np.all(d["deadline"] > 0)


def test_trace_counts_and_arrival_slots():
    d = traffic.draw({"generator": "trace", "n_tasks": 5000,
                      "pattern": "bursty", "horizon": 1440}, 1, 0, 0)
    assert d["arrival"].shape[0] == 5000
    assert np.unique(d["arrival"]).shape[0] == 5000 // 512 + 1
    with pytest.raises(ValueError):
        traffic.draw({"generator": "trace", "n_tasks": 10,
                      "pattern": "weekly"}, 1, 0, 0)


def test_a_mix_can_bring_its_own_generator(tmp_path, monkeypatch):
    (tmp_path / "two_slots.py").write_text(
        "import numpy as np\n"
        "def draw(rng, lib, n_tasks):\n"
        "    d = {f: lib[f][rng.integers(20, size=n_tasks)]\n"
        "         for f in ('p0', 'gamma', 'c', 'big_d', 'delta', 't0')}\n"
        "    u = np.full(n_tasks, 0.5)\n"
        "    a = np.arange(n_tasks, dtype=float) % 2 + 1\n"
        "    return dict(arrival=a, deadline=a + (d['big_d'] + d['t0']) / u,\n"
        "                utilization=u, **d)\n")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", str(tmp_path))
    d = traffic.draw({"generator": "two_slots", "entry": "online",
                      "n_tasks": 6}, 2**31 + 3, traffic.WINDOW, 0)
    assert d["arrival"].tolist() == [1, 2, 1, 2, 1, 2]
    assert np.all(d["deadline"] > d["arrival"])
