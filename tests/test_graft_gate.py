"""get_mesh must never silently truncate to fewer devices than asked
for, and the driver entry points stay callable."""

import pytest


def test_get_mesh_raises_on_insufficient_devices():
    from spark_tpu.parallel.mesh import get_mesh

    with pytest.raises(RuntimeError, match="only .* visible"):
        get_mesh(1024)


def test_get_mesh_exact_count():
    from spark_tpu.parallel.mesh import get_mesh

    mesh = get_mesh(8)
    assert mesh.devices.size == 8


def test_dryrun_multichip_runs_on_a_virtual_mesh():
    """The dry run is a plain CPU child: its environment alone (eight
    virtual devices) decides the platform, whatever the parent holds."""
    import __graft_entry__ as g

    g.dryrun_multichip(8)
