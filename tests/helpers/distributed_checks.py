"""Multi-device checks, run in a subprocess with 8 forced host devices.

Prints one "PASS <name>" line per check; the pytest wrapper asserts all.
Kept in one script so the jax import cost is paid once.
"""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual host devices, never the TPU

import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.launch.mesh import make_mesh
from repro.optim import compress
from repro.checkpoint.manager import CheckpointManager, reshard
from repro.launch import hlo_analysis, steps as steps_mod
from repro.optim import adamw
from repro.configs import get_arch
from repro.configs.base import ShapeCell


def check(name, ok):
    print(("PASS " if ok else "FAIL ") + name, flush=True)
    return ok


results = []

# ---------------------------------------------------------------------------
# 1. compressed_psum == psum (within int8 tolerance)
# ---------------------------------------------------------------------------
mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 1000)), jnp.float32)


def f_exact(x):
    return jax.lax.psum(x, "data")


def f_comp(x):
    return compress.compressed_psum(x, "data")


exact = jax.shard_map(
    f_exact, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
)(x)
comp = jax.shard_map(
    f_comp, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
)(x)
rel = float(jnp.max(jnp.abs(exact - comp)) / jnp.max(jnp.abs(exact)))
results.append(check(f"compressed_psum_parity rel_err={rel:.4f}", rel < 0.02))

# wire format really is int8: the lowered HLO's all-to-all/all-gather are s8
lowered = jax.jit(
    jax.shard_map(f_comp, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
              check_vma=False)
).lower(x)
txt = lowered.compile().as_text()
import re
coll_lines = [
    l for l in txt.splitlines()
    if re.search(r"= \S* ?(all-to-all|all-gather)", l)
]
int8_wire = any("s8[" in l for l in coll_lines)
results.append(check(f"int8_wire_format n_coll={len(coll_lines)}", int8_wire))

# ---------------------------------------------------------------------------
# 2. error feedback: compressed training matches uncompressed closely
# ---------------------------------------------------------------------------
w_true = jnp.asarray(rng.normal(size=(32,)), jnp.float32)


def data_batch(i):
    r = np.random.default_rng(i)
    X = jnp.asarray(r.normal(size=(8, 16, 32)), jnp.float32)  # per-device shard
    y = jnp.einsum("dbi,i->db", X, w_true)
    return X, y


def grad_fn(w, X, y):
    pred = jnp.einsum("bi,i->b", X, w)
    return jax.grad(lambda w: jnp.mean((jnp.einsum("bi,i->b", X, w) - y) ** 2))(w)


def run_sgd(compressed, steps=60, lr=0.05):
    w = jnp.zeros((32,))
    resid = jnp.zeros((32,))

    @jax.jit  # one compile per run, not one eager shard_map trace per step
    def step_fn(w, resid, X, y):
        def local(w, resid, X, y):
            X, y = X[0], y[0]  # drop the sharded singleton leading axis
            g = grad_fn(w, X, y)
            if compressed:
                (g,), (resid,) = compress.compressed_grad_tree(
                    (g,), (resid,), "data"
                )
            else:
                g = jax.lax.pmean(g, "data")
            return w - lr * g, resid

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P()),
            check_vma=False,
        )(w, resid, X, y)

    for i in range(steps):
        X, y = data_batch(i)
        w, resid = step_fn(w, resid, X, y)
    return w


w_plain = run_sgd(False)
w_comp = run_sgd(True)
err_plain = float(jnp.linalg.norm(w_plain - w_true))
err_comp = float(jnp.linalg.norm(w_comp - w_true))
results.append(
    check(
        f"error_feedback_convergence plain={err_plain:.4f} comp={err_comp:.4f}",
        err_comp < max(2 * err_plain, 0.05),
    )
)

# ---------------------------------------------------------------------------
# 3. elastic re-mesh: checkpoint on (2,4), restore onto (4,2) and (8,1)
# ---------------------------------------------------------------------------
import tempfile

arch = get_arch("gemma3-12b")
cfg = arch.smoke
params = arch.init(jax.random.PRNGKey(0), cfg)
from repro.parallel import sharding as shd

mesh_a = make_mesh((2, 4), ("data", "model"))
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(5, {"params": params})
    ok = True
    for shape in [(4, 2), (8, 1)]:
        mesh_b = make_mesh(shape, ("data", "model"))
        specs = shd.param_specs(params, arch, mesh_b)
        shardings = steps_mod.named(mesh_b, specs)
        _, restored = mgr.restore_latest({"params": params})
        placed = reshard(restored["params"], {"params": shardings}["params"])
        # value-identical after resharding
        flat_a = jax.tree_util.tree_leaves(params)
        flat_b = jax.tree_util.tree_leaves(placed)
        ok &= all(
            np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
            for a, b in zip(flat_a, flat_b)
        )
        # and usable: loss computes under the new mesh
        batch = arch.smoke_batch(seed=1)
        with mesh_b:
            loss, _ = jax.jit(lambda p, b: arch.loss_fn(cfg, p, b))(placed, batch)
        ok &= bool(jnp.isfinite(loss))
results.append(check("elastic_remesh_2x4_to_4x2_to_8x1", ok))

# ---------------------------------------------------------------------------
# 4. small-mesh dry-run + hlo_analysis sanity on a sharded train step
# ---------------------------------------------------------------------------
cell = ShapeCell("t", 64, 8, "train")
specs_in = {
    "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
    "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
}
params_abs, opt_abs = steps_mod.abstract_train_state(arch, cfg)
with mesh_a, steps_mod.activation_policy(arch, cell, mesh_a):
    psh, osh, bsh = steps_mod.train_shardings(
        arch, cfg, mesh_a, cell, params_abs, opt_abs, specs_in
    )
    fn = steps_mod.make_train_step(arch, cfg, adamw.AdamWConfig())
    compiled = (
        jax.jit(fn, in_shardings=(psh, osh, bsh), out_shardings=(psh, osh, None))
        .lower(params_abs, opt_abs, specs_in)
        .compile()
    )
counts = hlo_analysis.analyze(compiled.as_text())
ok = counts.flops > 1e6 and counts.collective_bytes > 0 and not counts.warnings
results.append(
    check(
        f"small_dryrun_analysis flops={counts.flops:.3g} "
        f"coll={counts.collective_bytes:.3g}",
        ok,
    )
)

print("ALL_OK" if all(results) else "SOME_FAILED")
sys.exit(0 if all(results) else 1)
