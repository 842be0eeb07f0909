"""What the configurations make, pinned to the numbers the harness gave
before a configuration brought its own task, data, split and loss
(PERF.md §6, PR 16): ``round_work`` of each cell at full size, from
shapes alone, and the population of each tiny cell from one seed."""
from __future__ import annotations

import hashlib

import pytest

from chipbench_cells import harness, tiny_cell

WORK = {
    "femnist.cyclepsl": {"live": 178, "rows": 5696,
                         "flops": 737457930240,
                         "fused_adam_bytes": 32902374400,
                         "feature_resample_bytes": 142946816},
    "resnet9.cyclesfl.cut4": {"live": 25, "rows": 800,
                              "flops": 2304026214400,
                              "fused_adam_bytes": 4635008000,
                              "feature_resample_bytes": 104864000},
    "femnist.sglr": {"live": 178, "rows": 5696,
                     "flops": 588234031104,
                     "fused_adam_bytes": 443032576,
                     "feature_resample_bytes": 0},
}

# sha256 of x's bytes then y's, with their shapes and dtypes
FEMNIST_POP = ((12, 10, 28, 28, 1), (12, 10), "4dae92c49cfcd15021c0e95a4140"
               "271f863344c98d17ddd4fbd5d7a33119f31d")
POPULATION = {
    "femnist.cyclepsl": FEMNIST_POP,
    "resnet9.cyclesfl.cut4": ((12, 10, 32, 32, 3), (12, 10),
                              "d9b618d7861121c9dd0c1d5d6667668d8513ddf21311"
                              "8342e3b8112e47bea5c4"),
    "femnist.sglr": FEMNIST_POP,
}


@pytest.mark.parametrize("name", sorted(WORK))
def test_round_work_is_the_parents(name):
    assert harness.round_work(harness.load_cell(name)) == WORK[name]


@pytest.mark.parametrize("name", sorted(POPULATION))
def test_tiny_population_is_the_parents(name):
    cell = tiny_cell(name)
    x, y = cell.model.population(cell.config, 2 ** 33 + 5)
    x_shape, y_shape, digest = POPULATION[name]
    assert (x.shape, x.dtype.name, y.shape, y.dtype.name) == \
        (x_shape, "float32", y_shape, "int32")
    assert hashlib.sha256(x.tobytes() + y.tobytes()).hexdigest() == digest
