import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench.tests.tiny import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture(scope="module")
def trained_root(tmp_path_factory):
    # 64^3 over 16^3 tiles with a 4 MiB budget: a reservoir large enough that
    # two epochs of training give an enhancer that lifts PSNR clearly above
    # the 1 dB limit, with live groups whose output a control can disturb
    return make_root(tmp_path_factory.mktemp("trained"), side=64, tile=16,
                     config={"mem_budget": 4 << 20}, limits={"enh_gain_db": 1.0})
