"""A whole run on the CPU, with the chip check skipped and the timed path
broken underneath: ``correct`` must come out false for each fault a cell
can have, and true when nothing is broken. Also the control: the
reference in bfloat16 in the program's place fails the comparison."""
import numpy as np
import pytest

from bench import control, harness


def tiny(root, name):
    cell = harness.Cell(root, name)
    cell.config["records"] = 2000
    cell.config["server"]["max_batch"] = 2
    # narrow records only: fewer exact-stage width branches to compile
    cell.config["layers"] = [l for l in cell.config["layers"]
                             if l.get("verts", 0) <= 16]
    cell.traffic.update(max_requests=4096, warm_seconds=0.2, clients=3,
                        pool=64)
    return cell


def half_batch(res):
    """Half of each batch left out: its rows come back empty."""
    for i in range(len(res.ids) // 2, len(res.ids)):
        res.ids[i] = res.ids[i][:0]


def altered(res):
    """One answer altered where it is produced: a row loses its first id,
    or gains id 0 when it has none."""
    row = res.ids[0]
    res.ids[0] = row[1:] if len(row) else np.zeros(1, row.dtype)


def one(monkeypatch, root, name, fault):
    from repro.core.engine import SpatialIndex

    monkeypatch.setattr(harness, "enable_cache", lambda root: None)
    if fault is not None:
        query = SpatialIndex.query

        def broken(self, *a, **kw):
            res = query(self, *a, **kw)
            fault(res)
            return res

        monkeypatch.setattr(SpatialIndex, "query", broken)
    return harness.run_cell(tiny(root, name), 987654321987, 0.6, False,
                            log=lambda *a: None, require_tpu=False)


@pytest.mark.parametrize("fault", [None, half_batch, altered],
                         ids=["sound", "half_batch", "altered"])
def test_window_run(monkeypatch, cells_root, fault):
    res = one(monkeypatch, cells_root, "mixed10m-tiles-closed96", fault)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"


def test_control_fails(cells_root):
    cell = tiny(cells_root, "mixed10m-tiles-closed96")
    cell.config["records"] = 20000
    got = control.control(cell, 3, 128)
    assert any(v > lim for v, lim in got.values()), got
