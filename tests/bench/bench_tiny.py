"""Test-size configurations of the benchmark's cells, for the CPU tests.

The shapes are cut so that a whole cell (set-up, window, check) runs on the
CPU in seconds; every other setting is the cell's own file."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ARCH = "olmo-1b-benchtest"


def load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "bench" / kind / f"{name}.json").read_text())


def clean_cfg() -> dict:
    cfg = load("configs", "chef-mimic")
    cfg.update(n_train=600, n_val=64, n_test=64, feature_dim=32,
               batch_size=100, n_epochs=2, budget=30)
    return cfg


def register_tiny_olmo():
    from repro.configs.base import _REGISTRY, get_config, reduced, register

    if TINY_ARCH not in _REGISTRY:
        register(TINY_ARCH)(lambda: reduced(get_config("olmo-1b")))


def olmo_cfg() -> dict:
    register_tiny_olmo()
    cfg = load("configs", "olmo-1b")
    cfg.update(repo_config=TINY_ARCH, hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, vocab_size=256)
    return cfg


def chat_traffic() -> dict:
    t = load("traffic", "chat")
    t.update(rate_per_s=20.0,
             prompt=dict(t["prompt"], median=16, min=4, max=48),
             output=dict(t["output"], median=6, min=2, max=16),
             slots=4, max_len=64,
             check=dict(t["check"], min_tokens=20, max_requests=3, pad_to=64))
    return t


def annotate_traffic() -> dict:
    t = load("traffic", "annotate")
    t.update(features=40, slots=4, max_len=64, rounds_planned=4,
             check=dict(t["check"], min_tokens=8, max_requests=8, pad_to=48))
    return t


def run_cell(driver, seconds: float = 0.5):
    """Set-up, window, release and check, as bench/run.py drives a cell."""
    driver.seconds = seconds
    driver.setup()
    driver.window(seconds)
    driver.release()
    checks = driver.check()
    _, failed = driver.attempted_failed()
    return failed == 0 and all(v <= lim for _, v, lim in checks), checks
