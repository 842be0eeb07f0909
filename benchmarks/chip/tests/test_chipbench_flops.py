"""The FLOP counter against hand counts of the configurations."""
from __future__ import annotations

import dataclasses

import pytest

from chipbench_cells import harness
from chipbench.flops import round_flops


def halves(name: str, cut: int):
    cell = harness.load_cell(name)
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, cut=cut))
    h = harness.forward_halves(cell)
    return h["client_flops"], h["server_flops"]


def test_femnist_cnn_per_image():
    # conv1 28*28*32*25*1, conv2 14*14*64*25*32 MACs; fc 3136*2048, head 2048*62
    client, server = halves("femnist.cyclepsl", 2)
    assert client == 2 * (28 * 28 * 32 * 25 + 14 * 14 * 64 * 25 * 32)
    assert server == 2 * (3136 * 2048 + 2048 * 62)
    assert client / 1e6 == pytest.approx(21.32, abs=0.01)
    assert server / 1e6 == pytest.approx(13.10, abs=0.01)


@pytest.mark.parametrize("cut,client_m,server_m", [(2, 154.5, 604.1),
                                                   (4, 456.6, 302.1)])
def test_resnet9_per_image(cut, client_m, server_m):
    client, server = halves("resnet9.cyclesfl.cut4", cut)
    assert client + server == 2 * (
        32 * 32 * 64 * 27 + 32 * 32 * 128 * 9 * 64 + 2 * 16 * 16 * 128 * 9 * 128
        + 16 * 16 * 256 * 9 * 128 + 8 * 8 * 512 * 9 * 256
        + 2 * 4 * 4 * 512 * 9 * 512 + 512 * 100)
    assert client / 1e6 == pytest.approx(client_m, abs=0.1)
    assert server / 1e6 == pytest.approx(server_m, abs=0.1)


def test_round_multipliers():
    rows = 178 * 32
    cycle = round_flops(21.3248e6, 13.099008e6, "cycle", rows, rows)
    assert cycle == pytest.approx(3 * 21.3248e6 * rows + 5 * 13.099008e6 * rows)
    assert cycle / 1e12 == pytest.approx(0.737, abs=0.001)
    sglr = round_flops(21.3248e6, 13.099008e6, "mean_grad", rows, 0)
    assert sglr == pytest.approx(3 * (21.3248e6 + 13.099008e6) * rows)
