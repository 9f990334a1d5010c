"""MoE shard-local dispatch: multi-device equivalence (subprocess).

The shard_map dispatch path must produce the same outputs as the
single-device held-expert layer on a real multi-device mesh (2 data x 2 model, with
experts split across the model axis and tokens across the data axis).
Runs in a subprocess because the 8-device XLA flag must be set before jax
initializes.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.models import moe as moe_mod
    from repro.models import transformer as T
    from repro.sharding.specs import SINGLE_POD_RULES, use_rules

    import dataclasses
    cfg = smoke_config("deepseek-moe-16b")  # 8 experts top-3, 1 shared
    # Capacity high enough that nothing drops: the sharded path enforces
    # capacity per (data-shard, expert) while the held-expert layer is
    # dropless, so only the drop-free regime is comparable.
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)

    ref = moe_mod._moe_held(layer0, x.reshape(-1, cfg.d_model), cfg)
    if "shared" in layer0:
        sh = layer0["shared"]
        xf = x.reshape(-1, cfg.d_model)
        g = xf @ sh["w_gate"]; u = xf @ sh["w_up"]
        ref = ref + (jax.nn.silu(g) * u) @ sh["w_down"]
    ref = ref.reshape(x.shape)

    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 2)
    with use_rules(mesh, SINGLE_POD_RULES):
        out = moe_mod.moe(layer0, x, cfg)
    d = float(jnp.abs(out - ref).max())
    print("MAXDIFF", d)
    assert d < 2e-5, d

    # Gradient path: shard_map backward (psum -> identity, all_gather ->
    # reduce-scatter) must be finite and nonzero.
    def loss(p):
        with use_rules(mesh, SINGLE_POD_RULES):
            return jnp.sum(moe_mod.moe(p, x, cfg) ** 2)
    g = jax.grad(loss)(layer0)
    gn = sum(float(jnp.sum(v * v)) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0, gn
    print("GRAD_OK", gn)

    # Quantized (serving) expert tree through the same shard_map dispatch.
    from repro.core.apply import quantize_params
    from repro.core.recipe import QuantRecipe
    q0 = quantize_params({"moe": layer0}, QuantRecipe(w_bits=8, ocs_ratio=0.05,
                                                      pad_to=16))["moe"]
    ref_q = moe_mod.moe(q0, x, cfg)  # no mesh -> local path
    with use_rules(mesh, SINGLE_POD_RULES):
        out_q = moe_mod.moe(q0, x, cfg)
    dq = float(jnp.abs(out_q - ref_q).max())
    assert dq < 2e-5, dq
    print("QUANT_OK", dq)
""")


@pytest.mark.slow
def test_moe_shardmap_multidevice_equivalence():
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "MAXDIFF" in r.stdout and "GRAD_OK" in r.stdout
