"""repro.analysis: the static verifier must pass the real kernels and
sharding profiles clean, and each seeded violation class must be caught
at error severity (mutation tests — the verifier's own test suite)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro import analysis
from repro.analysis import kernels as akernels
from repro.analysis import shard_lint
from repro.analysis.__main__ import main as analysis_main
from repro.kernels import KERNEL_REGISTRY, flash_attention
from repro.kernels.plan import KernelPlan

MESH_AXES = ("pod", "data", "model")


def errors(findings):
    return analysis.at_least(findings, "error")


# ---------------------------------------------------------------------------
# the real kernels and profiles verify clean
# ---------------------------------------------------------------------------

def test_all_registered_kernels_verify_clean():
    """Every registered kernel plan: zero errors AND zero warnings — the
    shipped tilings are fully aligned, race-free and within budget."""
    findings = akernels.verify_all()
    assert findings, "verifier must emit at least the vmem info findings"
    assert not analysis.at_least(findings, "warning"), \
        analysis.format_findings(findings)


def test_registry_covers_every_pallas_kernel_module():
    """Completeness: any kernels/*.py that builds a pallas_call must be
    registered for verification — new kernels cannot dodge the verifier."""
    import pathlib
    import repro.kernels as pkg
    pkg_dir = pathlib.Path(pkg.__file__).parent
    for mod in sorted(pkg_dir.glob("*.py")):
        if mod.name == "__init__.py":
            continue
        if "pallas_call(" in mod.read_text():  # call site, not prose
            assert mod.stem in KERNEL_REGISTRY, \
                f"{mod.name} builds a pallas_call but is not registered"


@pytest.mark.parametrize("arch,profiles", [
    ("qwen2-1.5b", ("2d", "fsdp", "sp", "expert")),
    ("gin-tu", ("2d",)),
    ("two-tower-retrieval", ("2d",)),
])
def test_sharding_profiles_lint_clean_at_error(arch, profiles):
    for profile in profiles:
        findings = shard_lint.lint_cell(arch, profile=profile)
        assert not errors(findings), analysis.format_findings(
            errors(findings))


# ---------------------------------------------------------------------------
# seeded mutations: each violation class must be flagged at error severity
# ---------------------------------------------------------------------------

def test_mutation_racing_out_spec_is_flagged():
    """Dropping flash attention's seq_axes declaration turns the benign
    nk-revisit accumulation into an undeclared write race."""
    plan = flash_attention.example_plan()
    mutated = dataclasses.replace(plan, seq_axes=())
    findings = akernels.verify_plan(mutated)
    race = [f for f in errors(findings) if f.check == "write-race"]
    assert race, analysis.format_findings(findings)


def test_mutation_non_trailing_seq_axis_is_flagged():
    """seq_axes must be the innermost grid axes; axis 0 of flash
    attention's (b*h, nq, nk) grid is not sequentially revisited."""
    plan = flash_attention.example_plan()
    mutated = dataclasses.replace(plan, seq_axes=(0,))
    race = [f for f in errors(akernels.verify_plan(mutated))
            if f.check == "write-race"]
    assert race


def test_mutation_match_keys_colliding_out_map_is_flagged():
    """Pointing every match_keys grid point at output block (0, 0) turns
    the race-free row tiling into an undeclared write race."""
    from repro.kernels import match_keys
    plan = match_keys.example_plan()
    mutated = dataclasses.replace(
        plan, out_specs=(pl.BlockSpec(plan.out_specs[0].block_shape,
                                      lambda i: (0, 0)),))
    race = [f for f in errors(akernels.verify_plan(mutated))
            if f.check == "write-race"]
    assert race, analysis.format_findings(akernels.verify_plan(mutated))


def test_mutation_bucket_assign_partial_boundary_block_is_flagged():
    """Shrinking bucket_assign's VMEM-resident boundary row to a block
    that no longer divides the padded boundary operand is an error."""
    from repro.kernels import bucket_assign
    plan = bucket_assign.example_plan()
    k_pad = plan.operands[1].shape[1]
    mutated = dataclasses.replace(
        plan, in_specs=(plan.in_specs[0],
                        pl.BlockSpec((1, k_pad - 1), lambda i: (0, 0))))
    div = [f for f in errors(akernels.verify_plan(mutated))
           if f.check == "block-divisibility"]
    assert div, analysis.format_findings(akernels.verify_plan(mutated))


def test_mutation_non_dividing_block_is_flagged():
    plan = KernelPlan(
        name="mutant_nondividing",
        grid=(2,),
        in_specs=(pl.BlockSpec((100, 128), lambda i: (i, 0)),),
        out_specs=(pl.BlockSpec((100, 128), lambda i: (i, 0)),),
        operands=(jax.ShapeDtypeStruct((256, 128), jnp.float32),),
        outputs=(jax.ShapeDtypeStruct((256, 128), jnp.float32),),
    )
    div = [f for f in errors(akernels.verify_plan(plan))
           if f.check == "block-divisibility"]
    assert div


def test_mutation_overbudget_vmem_scratch_is_flagged():
    """A 64 MiB f32 scratch buffer blows the 16 MiB per-kernel budget."""
    plan = flash_attention.example_plan()
    mutated = dataclasses.replace(
        plan, scratch_shapes=plan.scratch_shapes
        + (pltpu.VMEM((4096, 4096), jnp.float32),))
    over = [f for f in errors(akernels.verify_plan(mutated))
            if f.check == "vmem-budget"]
    assert over
    assert over[0].detail["vmem_bytes"] > over[0].detail["budget"]


def test_mutation_traced_index_map_closure_is_flagged():
    """An index map closing over a device array is a dynamic schedule —
    the exact hazard the verifier exists to catch statically."""
    trap = jnp.arange(4)
    plan = KernelPlan(
        name="mutant_traced_closure",
        grid=(4,),
        in_specs=(pl.BlockSpec((64, 128), lambda i: (trap[i], 0)),),
        out_specs=(pl.BlockSpec((64, 128), lambda i: (i, 0)),),
        operands=(jax.ShapeDtypeStruct((256, 128), jnp.float32),),
        outputs=(jax.ShapeDtypeStruct((256, 128), jnp.float32),),
    )
    pure = [f for f in errors(akernels.verify_plan(plan))
            if f.check == "index-purity"]
    assert pure


def test_mutation_out_of_bounds_index_map_is_flagged():
    plan = KernelPlan(
        name="mutant_oob",
        grid=(4,),
        in_specs=(pl.BlockSpec((64, 128), lambda i: (i, 0)),),
        out_specs=(pl.BlockSpec((64, 128), lambda i: (i + 1, 0)),),
        operands=(jax.ShapeDtypeStruct((256, 128), jnp.float32),),
        outputs=(jax.ShapeDtypeStruct((256, 128), jnp.float32),),
    )
    oob = [f for f in errors(akernels.verify_plan(plan))
           if f.check == "block-bounds"]
    assert oob


def test_mutation_replicated_100m_param_spec_is_flagged():
    """A 100M-param f32 tensor (400 MB) left fully replicated must be an
    error; a small replicated tensor must not."""
    big = jax.ShapeDtypeStruct((100_000_000,), jnp.float32)
    small = jax.ShapeDtypeStruct((128,), jnp.float32)
    findings = shard_lint.lint_spec_tree(
        {"w": big, "b": small}, {"w": None, "b": None}, MESH_AXES,
        subject="mutant")
    rep = [f for f in findings if f.check == "replicated-param"]
    assert len(rep) == 1
    assert rep[0].severity == "error"


def test_mutation_unknown_mesh_axis_is_flagged():
    findings = shard_lint.lint_spec_tree(
        (jax.ShapeDtypeStruct((64, 64), jnp.float32),),
        (P("data", "modle"),), MESH_AXES, subject="mutant")  # typo'd axis
    unknown = [f for f in errors(findings)
               if f.check == "unknown-mesh-axis"]
    assert unknown and unknown[0].detail["axis"] == "modle"


def test_mutation_malformed_traffic_is_flagged():
    t = np.ones((4, 4))                        # nonzero diag + fine sym
    diag = [f for f in shard_lint.lint_traffic(t, subject="m")
            if f.check == "traffic-diagonal"]
    assert diag and diag[0].severity == "error"
    t = np.zeros((4, 4))
    t[0, 1] = 5.0                              # asymmetric
    asym = [f for f in shard_lint.lint_traffic(t, subject="m")
            if f.check == "traffic-asymmetric"]
    assert asym and asym[0].severity == "error"


def test_identity_permute_pairs_stay_off_the_diagonal():
    """collectives.add_group_traffic: XLA's identity source->target pairs
    ({i,i}) move no link bytes and must not create self-traffic (which
    lint_traffic rejects)."""
    from repro.launch.collectives import add_group_traffic
    T = np.zeros((4, 4))
    add_group_traffic(T, np.array([[0, 0], [1, 2]]), 8.0)
    assert np.allclose(np.diag(T), 0.0)
    assert T[1, 2] == T[2, 1] == 16.0          # fwd+bwd ring links coincide


# ---------------------------------------------------------------------------
# wiring: strict sanitize, CLI, session.verify
# ---------------------------------------------------------------------------

def test_sanitize_spec_strict_matches_static_lint():
    """The runtime twin: the same spec the static lint flags must raise
    under sanitize_spec(strict=True)."""
    from repro.dist.sharding import sanitize_spec
    amesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    static = shard_lint.lint_spec_tree(
        (jax.ShapeDtypeStruct((8, 8), jnp.float32),),
        (P("pod", "model"),), ("data", "model"), subject="twin")
    assert errors(static)
    with pytest.raises(ValueError, match="pod"):
        sanitize_spec((8, 8), P("pod", "model"), amesh, strict=True)


def test_cli_kernels_suite_and_json_roundtrip(tmp_path):
    out = tmp_path / "findings.json"
    rc = analysis_main(["--suite", "kernels", "--json", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["gate"] == {"severity": "error", "failed": False}
    assert doc["counts"]["error"] == 0
    assert {f["check"] for f in doc["findings"]} >= {"vmem-budget"}


def test_session_verify_covers_kernels_and_traffic():
    from repro.launch.placement import PlacementSession
    session = PlacementSession(cache_dir="", map_restarts=0)
    findings = session.verify()
    assert not errors(findings)
    subjects = {f.subject for f in findings}
    assert any(s.startswith("kernels/") for s in subjects)


def test_finding_severity_validated_and_ranked():
    with pytest.raises(ValueError):
        analysis.Finding("x", "fatal", "s", "m")
    f1 = analysis.Finding("x", "info", "s", "m")
    f2 = analysis.Finding("x", "error", "s", "m")
    assert analysis.max_severity([f1, f2]) == "error"
    assert analysis.at_least([f1, f2], "warning") == [f2]
