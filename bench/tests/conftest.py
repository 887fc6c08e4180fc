"""Shared small cells: the benchmark's configurations and mixes at a size
a CPU can hold (two layers, narrow widths, a short window)."""
import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

import pytest  # noqa: E402

from bench import spec  # noqa: E402

TRAIN, CHAT = "smollm-360m.train-2k", "qwen2-0.5b.serve-chat"


def small_config(c, hidden=64, **deployment):
    c = copy.deepcopy(c)
    c.update(hidden_size=hidden, num_attention_heads=4, num_key_value_heads=2,
             num_hidden_layers=2, intermediate_size=2 * hidden, vocab_size=512,
             eos_token_id=0)
    c["deployment"].update(deployment)
    return c


def small_cell(name, hidden=64):
    cell = spec.Cell(name)
    t = copy.deepcopy(cell.traffic)
    if t["driver"] == "train":
        t.update(batch=4, seq=64,
                 documents=dict(t["documents"], median=16, min=2, max=64))
        return spec.Cell(name, config=small_config(cell.config, hidden), traffic=t)
    t.update(rate_per_s=20.0, check_tokens=30,
             prompt_len=dict(t["prompt_len"], median=8, min=2, max=32),
             output_len=dict(t["output_len"], median=6, min=2, max=16))
    return spec.Cell(name,
                     config=small_config(cell.config, hidden, n_slots=4, max_seq=128),
                     traffic=t)


@pytest.fixture(scope="session")
def program():
    from bench import system

    system.import_program()
    return system
