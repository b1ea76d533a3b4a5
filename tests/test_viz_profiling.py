"""viz CLI and profiling utilities."""

import numpy as np

from dexiraft_tpu.data.flow_io import write_flo


def test_viz_cli_converts_tree(tmp_path):
    from dexiraft_tpu.viz_cli import main

    d = tmp_path / "flows" / "seq"
    d.mkdir(parents=True)
    rng = np.random.default_rng(0)
    for i in range(2):
        write_flo(d / f"frame{i:04d}.flo",
                  rng.normal(size=(16, 24, 2)).astype(np.float32))
    out = tmp_path / "viz"
    main(["--input", str(tmp_path / "flows"), "--output", str(out)])
    import imageio.v2 as imageio

    # subdirectory structure is preserved (colliding frame names across
    # scenes must not overwrite)
    img = np.asarray(imageio.imread(out / "seq" / "frame0000.png"))
    assert img.shape == (16, 24, 3)


def test_trace_context(tmp_path):
    import jax
    import jax.numpy as jnp

    from dexiraft_tpu.profiling import trace

    with trace(str(tmp_path)):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    # trace files land under the dir
    assert any(tmp_path.rglob("*"))
