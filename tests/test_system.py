"""End-to-end behaviour tests for the paper's system: the three workflow
classes of §II running through the full middleware stack."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (ExecutionPolicy, ResourceDescription,
                        ResourceRequirements, Rhapsody, ServiceDescription,
                        TaskDescription, TaskKind)
from repro.core.agent import AgentConfig, run_agent_population
from repro.core.coupling import make_store
from repro.serving.client import llm_service_factory
from repro.substrate.simulation import heat_stencil, noop, surrogate_eval


def demo_cfg():
    return get_config("rhapsody-demo").scaled(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab=512)


def test_heterogeneous_campaign():
    """§II-A: concurrent serial/MPI/CPU/GPU tasks with dependencies."""
    rh = Rhapsody(ResourceDescription(nodes=4, cores_per_node=8,
                                      gpus_per_node=2), n_workers=4)
    try:
        descs = []
        for i in range(6):
            sim = TaskDescription(
                kind=TaskKind.EXECUTABLE, fn=heat_stencil,
                kwargs={"n": 32, "steps": 4, "seed": i},
                requirements=ResourceRequirements(ranks=2, cores_per_rank=2),
                task_type="mpi_sim")
            score = TaskDescription(
                fn=surrogate_eval, kwargs={"dim": 16, "hidden": 32, "seed": i},
                requirements=ResourceRequirements(gpus_per_rank=1),
                task_type="gpu_score", dependencies=[sim.uid])
            descs.extend([sim, score])
        uids = rh.submit(descs)
        assert rh.wait(uids, timeout=60)
        assert rh.events.peak_hw() >= 2  # genuinely overlapped types
    finally:
        rh.close()


def test_inference_at_scale_roundtrip():
    """§II-B: persistent service + concurrent clients."""
    rh = Rhapsody(ResourceDescription(nodes=1, cores_per_node=8), n_workers=2)
    try:
        rh.add_service(ServiceDescription(
            name="llm", factory=llm_service_factory(
                demo_cfg(), max_num_seqs=4, max_len=64,
                prefill_buckets=(16,))))
        ep = rh.get_service("llm")
        futs = [ep.request({"prompt": [i + 1] * 6, "max_new_tokens": 3})
                for i in range(6)]
        outs = [f.result(timeout=300) for f in futs]
        assert all(len(o["tokens"]) == 3 for o in outs)
        inst = rh.services.instances["llm"]
        assert inst.servicer.stats.decode_calls > 0
    finally:
        rh.close()


@pytest.mark.parametrize("kind", ["memory", "filesystem"])
def test_coupled_simulation_inference(kind):
    """§II-C: sim -> store -> inference pairs with real array payloads."""
    rh = Rhapsody(ResourceDescription(nodes=1, cores_per_node=8), n_workers=2)
    store = make_store(kind)
    try:
        def sim(key, seed):
            rng = np.random.RandomState(seed)
            store.put(key, rng.normal(size=256).astype(np.float32))
            return True

        def infer(key):
            data = store.get(key, timeout=10)
            return float(np.mean(data))

        descs = []
        for i in range(8):
            s = TaskDescription(kind=TaskKind.COUPLED, fn=sim,
                                args=(f"k{i}", i), task_type="sim")
            f = TaskDescription(kind=TaskKind.COUPLED, fn=infer,
                                args=(f"k{i}",), dependencies=[s.uid],
                                task_type="infer")
            descs.extend([s, f])
        uids = rh.submit(descs)
        assert rh.wait(uids, timeout=60)
        st = store.stats.summary()
        assert st["puts"] == 8 and st["gets"] == 8
    finally:
        store.close()
        rh.close()


def test_agentic_control_loop():
    """§II-C agentic: decisions realized as HPC tasks with bounded lag."""
    rh = Rhapsody(ResourceDescription(nodes=2, cores_per_node=8), n_workers=2)
    try:
        rh.add_service(ServiceDescription(
            name="llm", factory=llm_service_factory(
                demo_cfg(), max_num_seqs=4, max_len=64,
                prefill_buckets=(16,))))
        cfgs = [AgentConfig(name=f"a{k}", service="llm", n_decisions=2,
                            tasks_per_decision=2,
                            decision_payload=lambda i: {
                                "prompt": [3, 1, 4, 1, 5],
                                "max_new_tokens": 2})
                for k in range(2)]
        out = run_agent_population(rh, cfgs)
        assert out["decisions"] == 4
        assert out["tasks"] == 8
        assert not out["errors"]
        lags = rh.events.realization_lag()
        assert lags and max(lags) < 30.0
    finally:
        rh.close()


def test_oversubscription_backfill():
    """Logical oversubscription: big blocked task doesn't starve small ones."""
    rh = Rhapsody(ResourceDescription(nodes=1, cores_per_node=4),
                  policy=ExecutionPolicy(backfill=True), n_workers=2)
    try:
        gate = threading.Event()

        def hold():
            gate.wait(5)
            return "held"

        big1 = TaskDescription(fn=hold, requirements=ResourceRequirements(
            ranks=1, cores_per_rank=3), task_type="big")
        big2 = TaskDescription(fn=hold, requirements=ResourceRequirements(
            ranks=1, cores_per_rank=3), task_type="big")
        smalls = [TaskDescription(fn=noop, task_type="small")
                  for _ in range(10)]
        rh.submit([big1, big2] + smalls)  # big2 blocks; smalls backfill
        assert rh.wait([d.uid for d in smalls], timeout=5), \
            "small tasks must backfill around the blocked large task"
        gate.set()
        assert rh.wait([big1.uid, big2.uid], timeout=10)
    finally:
        rh.close()
