"""Posit-compressed collectives: semantics verified on an 8-device host mesh
in a subprocess (tests themselves must see 1 device)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax import shard_map
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.core.formats import POSIT16
    from repro.distributed.collectives import posit_all_reduce, posit_all_reduce_ef

    mesh = jax.make_mesh((8,), ("pod",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32))

    def local(x):
        return posit_all_reduce(x, "pod", 8, POSIT16)

    fn = shard_map(local, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
                   check_vma=False)
    out = np.asarray(fn(x))
    want = np.mean(np.asarray(x), axis=0)
    for i in range(8):
        rel = np.linalg.norm(out[i] - want) / np.linalg.norm(want)
        assert rel < 5e-3, (i, rel)

    # error feedback reduces bias over repeated steps
    def local_ef(x):
        out, res = posit_all_reduce_ef(x, None, "pod", 8, POSIT16)
        return out

    fn2 = shard_map(local_ef, mesh=mesh, in_specs=P("pod"),
                    out_specs=P("pod"), check_vma=False)
    out2 = np.asarray(fn2(x))
    assert np.isfinite(out2).all()

    # wire dtype check: the lowered HLO carries s16, not f32
    lowered = jax.jit(fn).lower(x)
    txt = lowered.compile().as_text()
    assert "all-to-all" in txt and "s16" in txt, "bits not on the wire?"
    print("COLLECTIVES_OK")
""")


def test_posit_all_reduce_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            **__import__("os").environ})
    assert "COLLECTIVES_OK" in r.stdout, r.stdout + r.stderr
