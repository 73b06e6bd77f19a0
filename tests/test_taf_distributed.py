"""Distributed TAF execution: the shard_map path on 8 placeholder devices
(subprocess so the device count doesn't leak into other tests)."""
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    assert len(jax.devices()) == 8
    from repro.core.tgi import TGI, TGIConfig
    from repro.data.temporal_graph_gen import generate
    from repro.storage.kvstore import DeltaStore
    from repro.taf import analytics, build_sots
    from repro.taf import exec as taf_exec

    events = generate(2500, seed=2)
    cfg = TGIConfig(n_shards=2, parts_per_shard=2, events_per_span=900)
    tgi = TGI.build(events, cfg, DeltaStore(m=2, r=1, backend="mem"))
    t0g, t1g = events.time_range()
    t0, t1 = int(t0g + 0.3 * (t1g - t0g)), int(t0g + 0.8 * (t1g - t0g))
    sots = build_sots(tgi, t0, t1)
    tm = (t0 + t1) // 2
    got = taf_exec.sharded_degree_at(sots, tm)           # 8-way shard_map
    _, want = analytics.degree_series_delta(sots, points=[tm])
    on = sots.init_present == 1
    np.testing.assert_allclose(got[on].astype(float), want[on, 0])
    print("DISTRIBUTED_OK", len(sots))
    """
)


def test_sharded_taf_on_8_devices():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        # the 8 host devices are the child's only devices on any machine
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=540,
    )
    assert "DISTRIBUTED_OK" in out.stdout, out.stderr[-2000:]
