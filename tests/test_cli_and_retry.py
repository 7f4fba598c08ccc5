"""Runnable-config CLIs + driver retry loop tests (reference:
``DL/models/*/Train.scala`` scopt mains; failure injection mirrors
``DLT/optim/DistriOptimizerSpec.scala:108`` which trains through an
exception-throwing layer and recovers from checkpoints)."""

import glob
import os

import numpy as np
import jax
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset.dataset import DataSet, TensorDataSet
from bigdl_tpu import optim


def test_lenet_cli(tmp_path):
    from bigdl_tpu.models import lenet

    params, state = lenet.main([
        "-b", "32", "-e", "1", "--learningRate", "0.1",
        "--checkpoint", str(tmp_path),
    ])
    assert params is not None
    assert glob.glob(str(tmp_path / "*")), "checkpoint files written"


def test_resnet_cli():
    from bigdl_tpu.models import resnet

    params, _ = resnet.main(["--maxIteration", "2", "-b", "8", "--depth", "8"])
    assert params is not None


def test_rnn_cli():
    from bigdl_tpu.models import rnn

    # batch divisible by the 8 virtual devices (conftest forces an
    # 8-device CPU mesh, so the optimizer factory picks DistriOptimizer)
    params, _ = rnn.main(["--maxIteration", "2", "-b", "8",
                          "--seqLength", "8", "--hiddenSize", "8"])
    assert params is not None


@pytest.mark.slow  # VGG16 end-to-end through the CLI (~40 s); the CLI
# plumbing itself is covered by the fast non-VGG legs below
def test_vgg_caffe_inference_cli(tmp_path):
    """The BASELINE 'VGG-16 Caffe-loaded inference' runnable config."""
    from bigdl_tpu.interop.caffe import save_caffe
    from bigdl_tpu.models import vgg

    model = vgg.build_vgg16(class_num=10)
    params, state = model.init(jax.random.key(0))
    proto = str(tmp_path / "vgg.prototxt")
    weights = str(tmp_path / "vgg.caffemodel")
    save_caffe(model, params, state, proto, weights, input_shape=(1, 3, 224, 224))

    top1 = vgg.main(["--from-caffe", proto, weights, "-b", "2", "--iters", "1"])
    assert top1.shape == (2,)


class _FailingOnce:
    """Raises once at a given iteration, then heals (the host-side analogue
    of the reference's exception-throwing 'mserf' layer)."""

    def __init__(self, at: int):
        self.at = at
        self.count = 0
        self.fired = False

    def __call__(self):
        self.count += 1
        if self.count == self.at and not self.fired:
            self.fired = True
            raise RuntimeError("injected failure (reference mserf layer)")


class _FailingDataSet(TensorDataSet):
    def __init__(self, x, y, failer):
        super().__init__(x, y)
        self.failer = failer

    def batches(self, batch_size, train, partial_batch=False):
        for b in super().batches(batch_size, train, partial_batch):
            self.failer()
            yield b


def test_checkpoint_retry_recovers_from_injected_failure(tmp_path, monkeypatch):
    """Training must survive a mid-run failure by reloading the newest
    checkpoint and continuing (reference retry window
    ``DistriOptimizer.scala:881-960``)."""
    rs = np.random.RandomState(0)
    x = rs.rand(64, 4).astype("float32")
    y = (x.sum(axis=1) > 2).astype("int32")
    failer = _FailingOnce(at=6)
    ds = _FailingDataSet(x, y, failer)

    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2), nn.LogSoftMax())
    from bigdl_tpu.core.config import EngineConfig

    config = EngineConfig().replace(failure_retry_times=3,
                                    failure_retry_interval_sec=0.0)
    opt = optim.LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                               batch_size=16, config=config)
    opt.host_prefetch_depth = 0  # keep the injected raise on the main thread
    opt.set_optim_method(optim.SGD(learning_rate=0.5))
    opt.set_end_when(optim.Trigger.max_iteration(60))
    opt.set_checkpoint(str(tmp_path), optim.Trigger.several_iteration(2))
    params, state = opt.optimize()

    assert failer.fired, "failure was never injected"
    assert opt.state.iteration >= 60, "training did not complete after retry"
    # recovery (not convergence speed) is under test: loss must be finite
    # and below the untrained ln(2) baseline after resuming
    assert np.isfinite(opt.state.loss) and opt.state.loss < 0.68


def test_retry_gives_up_after_budget(tmp_path):
    """Persistent failures must re-raise after failure_retry_times."""

    class _AlwaysFail(TensorDataSet):
        def batches(self, batch_size, train, partial_batch=False):
            raise RuntimeError("permanently broken pipeline")

    from bigdl_tpu.core.config import EngineConfig

    x = np.random.rand(32, 4).astype("float32")
    y = np.zeros(32, "int32")
    config = EngineConfig().replace(failure_retry_times=2,
                                    failure_retry_interval_sec=0.0)
    model = nn.Sequential(nn.Linear(4, 2), nn.LogSoftMax())
    opt = optim.LocalOptimizer(model, _AlwaysFail(x, y), nn.ClassNLLCriterion(),
                               batch_size=16, config=config)
    opt.host_prefetch_depth = 0
    opt.set_checkpoint(str(tmp_path), optim.Trigger.several_iteration(2))
    opt.set_end_when(optim.Trigger.max_iteration(4))
    with pytest.raises(RuntimeError, match="permanently broken"):
        opt.optimize()


def test_perf_cli_runs(capsys):
    """Perf harness (DistriOptimizerPerf/Perf.scala analogue) runs and
    emits a JSON record for both modes."""
    import json

    from bigdl_tpu.models import perf

    perf.main(["--model", "lenet", "-b", "8", "--mode", "train",
               "--classNum", "10", "--iters", "2"])
    perf.main(["--model", "lenet", "-b", "8", "--mode", "fwd",
               "--classNum", "10", "--iters", "2"])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert len(lines) == 2
    rec = json.loads(lines[0])
    assert rec["model"] == "lenet" and "records_per_sec" in rec


def _load_root_module(name):
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "..", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("script", ["chip_smoke", "bench"])
def test_chip_commands_fail_loudly_without_a_tpu(script, capsys):
    """``python chip_smoke.py`` / ``python bench.py`` measure the chip: on
    the CPU they exit non-zero before any work and print no result —
    never a CPU run reported under a device's name."""
    mod = _load_root_module(script)
    with pytest.raises(SystemExit) as e:
        mod.main([])
    assert e.value.args and e.value.args[0] not in (0, None)
    assert "needs a TPU" in str(e.value) or "measures a TPU" in str(e.value)
    assert '"ok"' not in capsys.readouterr().out


def test_bench_has_no_peak_for_an_unlisted_device():
    bench = _load_root_module("bench")
    assert bench.spec_peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit, match="no published peak"):
        bench.spec_peak("TPU v99")


@pytest.mark.parametrize("case", ["env_set", "env_unset", "cpu_only"])
def test_compile_cache_directory(case, monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code (JAX reads
    the variable). Unset: the FIXED ``<checkout>/.jax_cache``. A process
    held to the CPU gets no cache at all."""
    import bigdl_tpu
    from bigdl_tpu.core.engine import enable_compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(type(jax.config), "jax_platforms",
                        "cpu" if case == "cpu_only" else None)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(bigdl_tpu.__file__)))
    if case == "cpu_only":
        assert enable_compile_cache() is None and not updates
        return
    if case == "env_set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(checkout, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == os.path.join(
            checkout, ".jax_cache")
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
