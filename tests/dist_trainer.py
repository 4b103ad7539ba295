"""Multi-process trainer workload for the subprocess loss-parity oracle.

Role parity: reference dist_mnist.py-style workloads driven by
test_dist_base.py — a deterministic small model whose per-step losses the
parent compares against a single-process run.  Each rank feeds ITS shard
of the deterministic global batch (trainer-local data, reference
semantics); the loss fetch is the cross-replica mean, so ranks print
identical full-batch losses.

Invoked by paddle_tpu.distributed.launch with the fleet env contract set;
writes one JSON line {"rank": r, "losses": [...]} to --out-<rank>.json.
"""
import json
import os
import sys


def build_model(use_fleet, strategy=None):
    """Shared between ranks and the parent's single-process oracle — the
    parity assertion is only meaningful if both run THIS model."""
    from paddle_tpu import layers
    from paddle_tpu.framework.program import Program, program_guard
    from paddle_tpu.initializer import ConstantInitializer
    from paddle_tpu.optimizer import MomentumOptimizer
    from paddle_tpu.param_attr import ParamAttr

    main_p, startup = Program(), Program()
    main_p.random_seed = 1
    with program_guard(main_p, startup):
        x = layers.data("x", [8])
        y = layers.data("y", [1])
        h = layers.fc(x, 16, act="relu", param_attr=ParamAttr(
            initializer=ConstantInitializer(0.1)), bias_attr=False)
        pred = layers.fc(h, 1, param_attr=ParamAttr(
            initializer=ConstantInitializer(0.2)), bias_attr=False)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = MomentumOptimizer(0.05, 0.9)
        if use_fleet:
            from paddle_tpu.distributed import fleet

            fleet.init(is_collective=True, strategy=strategy)
            fleet.distributed_optimizer(opt)
            fleet.minimize(loss)
        else:
            opt.minimize(loss)
    return main_p, startup, loss


def make_batch():
    import numpy as np

    rs = np.random.RandomState(0)
    return rs.randn(32, 8).astype("f4"), rs.randn(32, 1).astype("f4")


def run_dygraph(out_path, steps):
    """Dygraph DataParallel over real processes (reference
    TestParallelDyGraphRunnerBase oracle, test_dist_base.py:379):
    scale_loss + apply_collective_grads across ranks."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.distributed.parallel import DataParallel
    from paddle_tpu.distributed.parallel_env import init_parallel_env
    from paddle_tpu.dygraph.tensor import Tensor
    from paddle_tpu import nn

    init_parallel_env()
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))

    net = nn.Linear(8, 1, bias_attr=False)
    # deterministic init shared by every rank and the oracle
    net.weight._set_raw(jnp.asarray(np.full((8, 1), 0.1, "f4")))
    model = DataParallel(net)

    X, Y = make_batch()
    per = len(X) // nranks
    Xl = X[rank * per:(rank + 1) * per]
    Yl = Y[rank * per:(rank + 1) * per]

    losses = []
    lr = 0.05
    for _ in range(steps):
        pred = model(Tensor(Xl))
        diff = pred - Tensor(Yl)
        loss = pt.tensor.math.mean(diff * diff)
        scaled = model.scale_loss(loss)
        scaled.backward()
        model.apply_collective_grads()
        # manual SGD (keeps the oracle trivial)
        w = net.weight
        w._set_raw(w._value - lr * w.grad._value)
        w.grad = None
        # every rank reports the FULL-batch loss: mean of local losses
        from jax.experimental import multihost_utils

        all_losses = multihost_utils.process_allgather(
            np.asarray(loss._value))
        losses.append(float(np.mean(all_losses)))

    with open(out_path, "w") as f:
        json.dump({"rank": rank, "losses": losses}, f)


def main():
    # the localhost-cluster tests run every rank on the CPU backend:
    # the ranks inherit JAX_PLATFORMS=cpu from tests/conftest.py
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.parallel_env import init_parallel_env

    out_path = sys.argv[1]
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    if os.environ.get("PADDLE_TPU_TEST_DYGRAPH") == "1":
        run_dygraph(out_path, steps)
        return
    localsgd = os.environ.get("PADDLE_TPU_TEST_LOCALSGD") == "1"

    mesh = init_parallel_env()
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    nranks = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))

    strategy = DistributedStrategy()
    if localsgd:
        strategy.localsgd = True
        strategy.localsgd_configs = {"k_steps": 2}
    if os.environ.get("PADDLE_TPU_TEST_SHARDING") == "1":
        strategy.sharding = True
    main_p, startup, loss = build_model(use_fleet=True, strategy=strategy)

    # deterministic global batch, shard by rank (trainer-local data)
    X, Y = make_batch()
    per = len(X) // nranks
    Xl, Yl = X[rank * per:(rank + 1) * per], Y[rank * per:(rank + 1) * per]

    exe = pt.Executor(pt.CPUPlace(), mesh=mesh)
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)
    losses = []
    for _ in range(steps):
        out = exe.run(main_p, feed={"x": Xl, "y": Yl}, fetch_list=[loss],
                      scope=scope)
        losses.append(float(np.asarray(out[0]).ravel()[0]))

    with open(out_path, "w") as f:
        json.dump({"rank": rank, "losses": losses}, f)


if __name__ == "__main__":
    main()
