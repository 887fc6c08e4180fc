"""The whole training step's share of the chip's bf16 peak: window tokens
per second x model FLOPs per token (bench/flops.py) over the peak."""
from bench import flops


def read(run):
    if not run.get("steps"):
        return None
    per_token = flops.train_flops_per_token(run["config"], run["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * rate * per_token / run["peak"]["flops"]
