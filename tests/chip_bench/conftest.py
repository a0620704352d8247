"""Fixtures of the chip benchmark's CPU tests: its directory on the path,
the manifest, and each cell's files cut to a size a test run holds."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

#: every width cut down, the table rows uneven so that offsets matter
TINY_SIZES = {"n_dense": 4, "n_tables": 6, "table_rows": [64, 50, 64, 40, 64, 30],
              "embed_dim": 8, "mlp_dims": [32, 16], "cin_layers": [8, 8],
              "batch_size": 32, "multi_hot": 2, "pooling": "sum"}


@pytest.fixture(scope="session")
def manifest():
    from manifest import Manifest
    return Manifest(ROOT, BENCH)


def tiny_cell(manifest, cell):
    """The cell's configuration and traffic at the tiny size, with the
    configuration's own limits."""
    entry = manifest.workload(cell)
    conf = copy.deepcopy(manifest.config(entry["config"]))
    conf["sizes"] = {k: TINY_SIZES[k] for k in conf["sizes"]}
    traffic = dict(manifest.traffic(entry["traffic"]), ring_batches=8)
    traffic["hot_rows"] = min(traffic["hot_rows"], 4)
    if traffic["ckpt_every"]:
        traffic["ckpt_every"] = 4
    return conf, traffic
