"""CPU rehearsal of ``chip_smoke.py``: the script's phases run here at
a few thousand rows so the script cannot rot between chip runs.  The
device check is the one thing not rehearsed — the test stands in for
it (the script itself offers no way round ``require_tpu``)."""

import json

import pytest


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_smoke_phases_on_cpu(capsys):
    import chip_smoke
    device = chip_smoke.device_info()
    assert device["platform"] == "cpu"
    # the script reports the process's counters, as its own process
    # would have them: not what an earlier test file of this worker
    # left in the registry
    from spark_rapids_tpu.obs import registry as obsreg
    obsreg.reset_registry()
    # parity with the plain reference and zero fallbacks are asserted
    # inside the phases; any failure raises out of run()
    chip_smoke.run(rows=6000, seed=5, device=device, ici=False)
    out = _lines(capsys)
    assert out[-1] == {"ok": True, "device": device}
    phases = [o.get("phase") for o in out[:-1]]
    assert phases.count("q6") == 3 and phases.count("q3") == 1
    q6 = [o for o in out if o.get("phase") == "q6"]
    assert all(o["kernel.dispatches"] > 0 for o in q6), \
        "an execution did not reach the device (result cache?)"
    assert {o["lo"] for o in q6} == set(chip_smoke.Q6_BINDINGS)


def test_smoke_refuses_without_tpu(capsys):
    import chip_smoke
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""    # no result line, no data


def test_four_chip_smoke_phase_on_cpu(capsys, monkeypatch):
    """``--chips 4``: q65 through the placed ICI path, here on a mesh of
    four virtual devices at 16 scan batches of a few thousand rows."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import chip_smoke
    from spark_rapids_tpu.mem import device as devmgr
    from spark_rapids_tpu.shuffle import ici
    monkeypatch.setattr(ici, "_DEFAULT_MESH", Mesh(
        np.array(jax.devices()[:4]), ("shuffle",)))
    monkeypatch.setattr(jax, "devices", lambda *a: jax.local_devices()[:4])
    monkeypatch.setitem(
        chip_smoke.BASE_CONF,
        "spark.rapids.tpu.sql.reader.batchSizeRows", 5000)
    device = chip_smoke.device_info()
    try:
        chip_smoke.run(rows=64_000, seed=7, device=device, ici=True)
    finally:
        devmgr.initialize(2)
    out = _lines(capsys)
    assert out[-1] == {"ok": True, "device": device}
    q65 = [o for o in out if o.get("phase") == "ici_q65"]
    assert [o["execution"] for o in q65] == [1, 2]
    for o in q65:
        assert o["rows"] == 100 and o["kernel.dispatches"] > 0
        assert o["scan.placed.chips"] >= 4
        assert o["exchange.ici.exchanges"] == 3
        assert o["exchange.ici.movedBatches"] == 0
    vs = next(o for o in out if o.get("phase") == "ici_q65_vs_reference")
    assert vs["rows_diff"] == vs["key_mismatch"] == 0
    assert len(next(o for o in out if o.get("phase") == "ici_device_bytes")
               ["peak_bytes_in_use"]) == 4
