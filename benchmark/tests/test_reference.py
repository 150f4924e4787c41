"""The reference's quantizer against upstream's own expected values
(worldql_server `subscriptions/cube_area.rs:102-175`, as SURVEY.md
section 2 describes them), the comparison, and the float32 control."""

import numpy as np
import pytest

from benchmark.reference import ConnectedIndex, compare, cube_labels
from benchmark.tests.util import tiny_cell


@pytest.mark.parametrize("coord,size,label", [
    (0.0, 10, 10), (5.0, 10, 10), (10.0, 10, 10), (10.5, 10, 20),
    (15.0, 10, 20), (20.0, 10, 20), (-5.0, 10, -10), (-10.0, 10, -10),
    (-10.5, 10, -20), (-20.0, 10, -20), (-0.5, 10, -10), (0.5, 10, 10),
    (7.9, 8, 8), (8.0, 8, 8), (8.1, 8, 16), (-8.0, 8, -8), (-8.1, 8, -16),
    (512.0 + 2.0 ** -30, 16, 528), (512.0 - 2.0 ** -30, 16, 512),
    (-512.0 - 2.0 ** -30, 16, -528), (-512.0 + 2.0 ** -30, 16, -512),
])
def test_cube_label(coord, size, label):
    assert cube_labels([coord], size)[0] == label


def test_float32_moves_a_face_message_into_the_neighbour():
    c = np.array([512.0 + 2.0 ** -30, -512.0 - 2.0 ** -30])
    assert cube_labels(c, 16).tolist() == [528, -528]
    assert cube_labels(c, 16, np.float32).tolist() == [512, -512]


def test_expected_leaves_out_the_sender_unless_including_self():
    pos = np.array([[1.0, 1, 1], [2.0, 2, 2], [100.0, 1, 1]])
    index = ConnectedIndex(np.zeros(3, int), pos, 16)
    msg, peer = index.expected([0, 0], pos[[0, 0]], [0, 0], [False, True])
    assert sorted(zip(msg.tolist(), peer.tolist())) == [(0, 1), (1, 0), (1, 1)]
    msg, peer = index.expected([0], [[500.0, 1, 1]], [0], [True])
    assert len(msg) == 0


def test_compare_counts_missing_extra_duplicated():
    res = compare([0, 0, 1], [1, 2, 1], [0, 0, 0, 2], [1, 1, 3, 0], 4)
    assert (res["attempted"], res["missing"], res["extra"],
            res["duplicated"]) == (3, 2, 2, 1)
    assert res["good"].tolist() == [True, False, False, False]


@pytest.mark.parametrize("cell_name", ["crowd-1m.hot-cube",
                                       "crowd-1m.pair-flood"])
def test_float32_reference_in_the_programs_place_fails(cell_name):
    """The control of `correct`: deliveries as a float32 quantizer
    would make them, held to the float64 reference, must fail."""
    cell, deployment = tiny_cell(cell_name, seed=2 ** 31 + 11)
    plan = cell.traffic.plan(dict(cell.workload, rate=4000), deployment,
                             2 ** 31 + 11, 10.0, 1)
    exp = cell.traffic.expected(plan, deployment)
    low = cell.traffic.expected(plan, deployment, np.float32)
    n = len(deployment.connected)
    same = compare(*exp, *exp, n)
    assert same["missing"] == same["extra"] == same["duplicated"] == 0
    broken = compare(*exp, *low, n)
    assert broken["missing"] + broken["extra"] > 0
