"""Input-pipeline-inclusive training path: multiprocess DataLoader ->
uint8 feed -> on-device normalize -> chunked run_steps.  Small shapes on
the CPU; at full size it is not measured on the chip (no cell feeds a
trainer from a loader, ROADMAP W5)."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu.amp.static_amp import decorate
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.program import program_guard
from paddle_tpu.io import DataLoader, Dataset


class _TinyImages(Dataset):
    def __init__(self, n=128, shape=(3, 32, 32)):
        self.n, self.shape = n, shape

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        img = rs.randint(0, 256, self.shape, np.uint8)
        return img, np.array([i % 10], np.int64)


def test_uint8_chunked_training_pipeline():
    import jax

    from paddle_tpu.vision.static_models import resnet50_train_program

    # resnet50 is too heavy for CPU CI; reuse the builder's uint8 head
    # contract on a small custom net instead
    from paddle_tpu import layers
    from paddle_tpu.framework.program import Program
    from paddle_tpu.optimizer import MomentumOptimizer

    B, K = 8, 3
    main, startup = Program(), Program()
    main.random_seed = 1
    with unique_name.guard(), program_guard(main, startup):
        raw = layers.data("image", [3, 32, 32], dtype="uint8")
        img = layers.scale(layers.cast(raw, "float32"), 1.0 / 127.5,
                           bias=-1.0)
        img.shape = tuple(raw.shape)
        h = layers.conv2d(img, 8, 3, padding=1, act="relu")
        h = layers.pool2d(h, 2, pool_stride=2)
        logits = layers.fc(h, 10)  # fc flattens trailing dims itself
        label = layers.data("label", [1], dtype="int64")
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        MomentumOptimizer(0.05, 0.9).minimize(loss)

    exe = pt.Executor(pt.CPUPlace())
    scope = pt.framework.Scope()
    exe.run(startup, scope=scope)

    loader = DataLoader(_TinyImages(), batch_size=B, num_workers=2,
                        shuffle=False)
    it = iter(loader)

    def next_chunk():
        imgs, lbls = [], []
        for _ in range(K):
            im, lb = next(it)
            imgs.append(np.asarray(im))
            lbls.append(np.asarray(lb).astype("int32"))
        return {"image": np.stack(imgs), "label": np.stack(lbls)}

    losses = []
    for _ in range(2):
        out = exe.run_steps(main, feed=next_chunk(), fetch_list=[loss],
                            scope=scope)
        vals = np.asarray(out[0]).reshape(-1)
        assert vals.shape[0] == K
        losses.extend(float(v) for v in vals)
    assert all(np.isfinite(losses)), losses
    # uint8 feed dtype is preserved end-to-end (normalize on device)
    assert np.asarray(next_chunk()["image"]).dtype == np.uint8
