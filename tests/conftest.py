import os
import sys

import numpy as np
import pytest

# tests run on the single real CPU device — the 512-device dry-run is
# exercised via subprocess (test_dryrun_subprocess.py), never in-process.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def tiny_ecg():
    """Small synthetic ECG split shared across tests (generated once)."""
    from repro.data.ecg import make_ecg_dataset, train_val_split
    x, y = make_ecg_dataset(seed=0, n_samples=240, length=60000,
                            decimation=32)
    return train_val_split(x, y, seed=0)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
