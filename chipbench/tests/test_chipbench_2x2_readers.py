"""The readers of the cell ``paper208-2x2-equake``, on the CPU: each
returns nothing without a trace and the right share or time on a
hand-made reduction, and every cell asks for as many chips as its
configuration has tiles."""
import pytest

from chipbench import harness

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
READERS_2X2 = ["halo_share.2x2", "collective_share.2x2",
               "halo_ici_share.2x2", "device_ms_per_cycle.2x2",
               "device_idle_share.2x2"]

#: a reduction of two devices' traced window; the busiest is TPU:0
REDUCED_2X2 = {
    "window_s": 2.5, "busy_s_mean": 1.75,
    "devices": {
        "/device:TPU:0": {"busy_s": 2.0, "collective_s": 0.3,
                          "permute_s": 0.1, "ops": {}},
        "/device:TPU:1": {"busy_s": 1.5, "collective_s": 0.5,
                          "permute_s": 0.4, "ops": {}}}}
RECORD_2X2 = {"window_cycles": 100, "nodes": 208 * 208,
              "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_cells_ask_for_one_chip_per_tile(cell):
    tiles = harness.load_json(
        harness.HERE / "configs" / f"{cell['config']}.json")["tiles"]
    assert cell["chips"] == tiles[0] * tiles[1]


@pytest.mark.parametrize("name", READERS_2X2)
def test_2x2_readers_are_wired_to_the_cell(name):
    metric, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert metric["workloads"] == ["paper208-2x2-equake"]
    assert metric["moves"] == "node_cycles_per_s"


@pytest.mark.parametrize("name", READERS_2X2)
def test_2x2_readers_read_nothing_without_a_trace(name):
    assert harness.load_module("metrics", name).read(
        {"devices": {}, "window_s": 1.0}, {"window_cycles": 64}) is None


@pytest.mark.parametrize("name,want", [
    ("halo_share.2x2", 0.1 / 2.0),
    ("collective_share.2x2", 0.3 / 2.0),
    ("device_ms_per_cycle.2x2", 2.0e3 / 100),
    ("device_idle_share.2x2", 1 - 1.75 / 2.5),
    # four slabs of 104 flits x 10 int32 fields per cycle, over 1 ms of
    # permutes per cycle, over 1,600 Gbit/s
    ("halo_ici_share.2x2", 4 * 104 * 10 * 4 / 1e-3 / 200e9),
])
def test_2x2_readers_on_a_hand_made_reduction(name, want):
    read = harness.load_module("metrics", name).read
    assert read(REDUCED_2X2, RECORD_2X2) == pytest.approx(want)


def test_halo_roofline_needs_a_known_device_and_a_square_mesh():
    read = harness.load_module("metrics", "halo_ici_share.2x2").read
    assert read(REDUCED_2X2, dict(RECORD_2X2, device={"kind": "cpu"})) is None
    assert read(REDUCED_2X2, dict(RECORD_2X2, nodes=208 * 200)) is None
