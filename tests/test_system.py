"""End-to-end system behaviour: train driver, restart determinism,
compressed HSDP, and the dry-run machinery at test scale."""
import jax

from repro.launch.train import main as train_main


def test_train_driver_end_to_end():
    """Short end-to-end training run through the public driver."""
    loss = train_main([
        "--arch", "yi_9b", "--smoke", "--steps", "6", "--mesh", "4x2",
        "--fabric", "photonic", "--batch", "8", "--seq", "32",
        "--lr", "3e-3",
    ])["losses"][-1]
    assert loss < 7.0


def test_train_restart_is_deterministic(tmp_path):
    """Crash/restart: resuming from a checkpoint replays the same batches
    and reaches the same loss as an uninterrupted run."""
    ck = str(tmp_path / "ck")
    full = train_main([
        "--arch", "yi_9b", "--smoke", "--steps", "8", "--mesh", "4x2",
        "--batch", "8", "--seq", "32", "--lr", "1e-3",
    ])["losses"][-1]
    train_main([
        "--arch", "yi_9b", "--smoke", "--steps", "4", "--mesh", "4x2",
        "--batch", "8", "--seq", "32", "--lr", "1e-3",
        "--ckpt", ck, "--ckpt-every", "4",
    ])
    resumed = train_main([
        "--arch", "yi_9b", "--smoke", "--steps", "8", "--mesh", "4x2",
        "--batch", "8", "--seq", "32", "--lr", "1e-3",
        "--ckpt", ck, "--resume",
    ])["losses"][-1]
    assert abs(full - resumed) < 1e-4


def test_hsdp_compressed_training_converges():
    loss = train_main([
        "--arch", "yi_9b", "--smoke", "--steps", "6", "--mesh", "2x2x2",
        "--hsdp", "--compress", "--batch", "8", "--seq", "32",
        "--lr", "3e-3",
    ])["losses"][-1]
    assert loss < 7.0


def test_dryrun_cell_in_process():
    """The dry-run machinery lowers+compiles+extracts at test scale."""
    from repro.analysis.hlo_cost import corrected_cost
    from repro.launch import dryrun
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        fn, args = dryrun.input_specs("granite_moe_1b_a400m", "train_4k",
                                      mesh)
        compiled = jax.jit(fn).lower(*args).compile()
        cc = corrected_cost(compiled.as_text(), {"data": 4, "model": 2})
        assert cc.flops > 0
        assert cc.collective_bytes.get("total", {}).get("_bytes", 0) > 0
