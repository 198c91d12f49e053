"""Launchers + LocalSGD: real multi-process localhost worlds.

Replaces the reference's debug_launcher/gloo tests (ref tests/test_cpu.py,
test_grad_sync.py:51): N OS processes rendezvous through the JAX coordinator
on localhost, so cross-process collectives and LocalSGD averaging run for
real — the launch-and-assert pattern of SURVEY.md §4.
"""

import numpy as np
import pytest

from accelerate_tpu.launchers import debug_launcher, notebook_launcher


def _world_worker():
    import jax

    from accelerate_tpu.state import PartialState

    state = PartialState()
    assert state.num_processes == 2, state.num_processes
    assert jax.process_count() == 2


def _object_collective_worker():
    from accelerate_tpu.state import PartialState
    from accelerate_tpu.utils.operations import broadcast_object_list, gather_object

    state = PartialState()
    rank = state.process_index
    gathered = gather_object({"rank": rank})
    assert [g["rank"] for g in gathered] == [0, 1], gathered
    objs = broadcast_object_list([f"from-{rank}", rank * 10])
    assert objs == ["from-0", 0], objs


def _local_sgd_worker():
    import jax.numpy as jnp

    from accelerate_tpu.local_sgd import LocalSGD
    from accelerate_tpu.state import PartialState

    state = PartialState()
    rank = state.process_index
    params = {"w": jnp.full((4,), float(rank + 1))}
    with LocalSGD(local_sgd_steps=2) as lsgd:
        params = lsgd.step(params)  # step 1: no sync, stays local
        assert float(params["w"][0]) == rank + 1
        params = lsgd.step(params)  # step 2: boundary -> cross-host mean
        np.testing.assert_allclose(np.asarray(params["w"]), 1.5)
        params = lsgd.step(params)  # step 3: local again
        params = lsgd.flush(params)  # explicit final average
        np.testing.assert_allclose(np.asarray(params["w"]), 1.5)


def _failing_worker():
    raise ValueError("worker boom")


@pytest.mark.slow
def test_debug_launcher_world():
    debug_launcher(_world_worker, num_processes=2)


@pytest.mark.slow
def test_debug_launcher_object_collectives():
    debug_launcher(_object_collective_worker, num_processes=2)


@pytest.mark.slow
def test_debug_launcher_local_sgd():
    debug_launcher(_local_sgd_worker, num_processes=2)


@pytest.mark.slow
def test_debug_launcher_propagates_failure():
    with pytest.raises(RuntimeError, match="worker boom"):
        debug_launcher(_failing_worker, num_processes=2)


def test_notebook_launcher_runs_in_process():
    out = []
    notebook_launcher(out.append, args=(42,), num_processes=1)
    assert out == [42]


def test_local_sgd_single_process_passthrough():
    import jax.numpy as jnp

    from accelerate_tpu.local_sgd import LocalSGD

    params = {"w": jnp.ones((2,))}
    with LocalSGD(local_sgd_steps=4) as lsgd:
        assert not lsgd.enabled  # single process: disabled (ref local_sgd.py:30-36)
        out = lsgd.step(params)
    assert out is params


def test_local_sgd_rejects_bad_steps():
    from accelerate_tpu.local_sgd import LocalSGD

    with pytest.raises(ValueError):
        LocalSGD(local_sgd_steps=0)


def test_private_jax_probes_exist_on_the_installed_jax():
    """notebook_launcher's init-free accelerator probe leans on two
    private jax 0.9.0 entry points; they are imported outside any `try`,
    so a rename fails loudly. Pin the names and what they answer here."""
    from jax._src import hardware_utils, xla_bridge

    assert isinstance(xla_bridge.backends_are_initialized(), bool)
    chips, _device_id = hardware_utils.num_available_tpu_chips_and_device_id()
    assert chips == 0  # this sandbox has no chip


def test_notebook_launcher_ambient_platform_names():
    """An explicit JAX_PLATFORMS is authoritative for the "accelerator
    attached" decision, and only real platform names count."""
    import inspect

    from accelerate_tpu import launchers

    src = inspect.getsource(launchers.notebook_launcher)
    assert '("tpu", "gpu", "cuda", "rocm")' in src
    assert "except Exception" not in src
