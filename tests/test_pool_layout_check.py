"""``scripts/pool_layout_check.py`` at CPU size: for each cell's row widths
(Moonlight's latent row with its empty value plane, Danube's 8 x 80 heads,
Delphi's 12 x 10) the row pool, driven through the engine's functions,
ends every layer byte-equal to the per-head oracle."""
import importlib.util
import json
import os

import pytest

SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "pool_layout_check.py")


def _script():
    spec = importlib.util.spec_from_file_location("pool_layout_check",
                                                  SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", ["longctx", "decode", "cohort"])
def test_pool_layout_check_small(cell, tmp_path):
    check = _script()
    out = tmp_path / "check.json"
    assert check.main(["--small", "--cells", cell, "--out", str(out)]) == 0
    row, = json.loads(out.read_text())
    assert row["equal"] and not any(row["mismatched"].values())
    shape = check.small(check.CELLS[cell])
    assert row["pool_block_ids_max"] == shape["NB"] - 1
    assert row["layers_compared"] == shape["L"]
