import copy

import pytest

from bench import common

common.program_on_path()

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16)


def tiny_config(name: str) -> dict:
    """A configuration file of the benchmark at a size a test can hold."""
    cfg = copy.deepcopy(common.config(name))
    cfg.update(TINY)
    return cfg


def tiny_traffic(name: str) -> dict:
    tr = copy.deepcopy(common.traffic(name))
    if tr["window"] == "train":
        tr.update(seq=32, batch_per_worker=2)
    return tr


@pytest.fixture
def cpu_extra():
    """What ``run.run_cell`` needs to read a CPU backend's trace."""
    return {"trace_plane": "/host:CPU", "trace_line": None,
            "peak": common.peaks("TPU v5 lite")}
