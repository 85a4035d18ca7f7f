"""The in-tree work count at the two configurations' shapes, against
counts made by hand from work.py's list of what one superstep touches."""

import pytest

from perfbench import spec, work


def test_lanes():
    assert [work.lanes(f) for f in (1, 2, 6, 8, 36, 64)] == [1, 2, 8, 8,
                                                              64, 64]


def test_pong_shape():
    # p=16, D=9, F=6 -> Fp=8, UCT: 4 edge rows.
    # per level: 4*8 + 6 + 2 = 40 select words, 10 backup words -> 50
    # per worker: 9*50 + (1 + 18 + 2) + (18 + 5 + 4) = 498 words
    # 16 workers: 7968 words = 31872 bytes
    # ops: 16 * 9 * (8 * (16 + 2) + 6) = 21600
    got = work.per_slot_superstep(p=16, D=9, F=6, puct=False)
    assert got == {"ops": 21600, "bytes": 31872}


def test_gomoku_shape():
    # p=16, D=5, F=36 -> Fp=64, PUCT: 5 edge rows.
    # per level: 5*64 + 8 = 328 select words, 10 backup words -> 338
    # per worker: 5*338 + (1 + 10 + 2) + (10 + 5 + 4) = 1722 words
    # 16 workers: 27552 words = 110208 bytes
    # ops: 16 * 5 * (64 * (17 + 2) + 6) = 97760
    got = work.per_slot_superstep(p=16, D=5, F=36, puct=True)
    assert got == {"ops": 97760, "bytes": 110208}


@pytest.mark.parametrize("config", ["pong", "gomoku6"])
def test_bytes_bound_on_v5e(config):
    c = spec.load_json(spec.config_path(config))
    one = work.per_slot_superstep(p=c["service"]["p"], D=c["tree"]["D"],
                                  F=c["tree"]["F"],
                                  puct=c["tree"]["score_fn"] == "puct")
    t, bound = work.least_seconds(one, spec.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == one["bytes"] / 819e9
