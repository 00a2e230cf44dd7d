"""CPU rehearsal of each traffic mix at a tiny size: the drivers' set-up and
window, called directly."""

from __future__ import annotations

import pytest

from bench import drivers, harness


@pytest.mark.parametrize("config,mix", [
    ("synthetic512-g64", "volume"),
    ("synthetic512-g64", "solve"),
    ("synthetic512-g16-fused", "solve-serial"),
])
def test_mix_runs(tiny, config, mix):
    cfg = tiny(config)
    traffic = _Reads(harness.load_traffic(mix))
    if mix == "volume":
        traffic["max_slices"] = 3
    rec = harness.Recorder()
    window = drivers.load(traffic["driver"]).setup(harness.make_session(cfg), cfg, traffic, 77, rec)
    rec.on = True
    answers, attempted, failed, elapsed = drivers.run_window(window, 0.5)
    assert failed == 0 and elapsed > 0
    assert len(answers) == attempted >= window.unit
    assert all(a.status in ("converged", "max_iters") for a in answers)
    if mix == "volume":
        # Closed loop: fresh slices in order, each planned inside a span.
        assert [a.slice for a in answers] == list(range(len(answers)))
        assert len([s for s in rec.spans if s[0] == "plan"]) == len(answers)
    else:
        assert len(answers) % traffic["slices"] == 0
        assert {a.slice for a in answers} == set(range(traffic["slices"]))
    # Every parameter of the mix is read: by its driver, or by the harness.
    assert set(traffic) - traffic.read <= HARNESS_KEYS


class _Reads(dict):
    """A dict that notes which keys were read."""

    def __init__(self, *a):
        super().__init__(*a)
        self.read = set()

    def __getitem__(self, key):
        self.read.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.read.add(key)
        return super().get(key, default)


#: The keys of a traffic file that ``harness.run_cell`` reads itself.
HARNESS_KEYS = {"driver", "rate_metric", "trace_seconds"}


def test_harness_reads_its_keys():
    import inspect

    src = inspect.getsource(harness.run_cell)
    assert all(f'"{k}"' in src for k in HARNESS_KEYS)


@pytest.mark.parametrize("mix", sorted(p.stem for p in (harness.BENCH / "traffic").glob("*.json")))
def test_every_mix_names_a_driver(mix):
    driver = drivers.load(harness.load_traffic(mix)["driver"])
    assert callable(driver.setup) and callable(driver.checked_images)


def test_every_metric_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"])), m["name"]
    read = harness.load_reader("device.idle_share.solve")
    assert read.__module__ == "bench_metric_device.idle_share"


def test_volume_window_ends_when_the_slices_run_out(tiny):
    cfg = tiny("synthetic512-g64")
    traffic = dict(harness.load_traffic("volume"), max_slices=3)
    window = drivers.load("closed_loop").setup(harness.make_session(cfg), cfg, traffic, 5,
                                               harness.Recorder())
    answers, attempted, _, _ = drivers.run_window(window, 1e6)
    assert attempted == len(answers) == 3


def test_reservoir_samples_every_position_alike():
    import random

    hits = [0] * 10
    for seed in range(3000):
        r = drivers.Reservoir(2, random.Random(seed))
        live = set()
        for i in range(10):
            enters, evicted = r.offer(i)
            live.discard(evicted)
            if enters:
                live.add(i)
        assert live == set(r.kept) and len(live) == 2
        for i in live:
            hits[i] += 1
    assert min(hits) > 0.8 * 600 and max(hits) < 1.2 * 600  # 2/10 of 3000 each
