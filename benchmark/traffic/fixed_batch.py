"""Traffic for a training cell: one fixed seeded batch, resident on the
device and reused by every step (the input pipeline does nothing; a cell
that feeds through it is another generator).

Parameters (the workload file's ``traffic``): ``batch_per_chip``.
"""


def generate(params, model, config, seed, chips):
    """(global batch size, {feed name: numpy array}) for ``chips``
    shards; the same seed gives the same batch."""
    batch = int(params["batch_per_chip"]) * chips
    return batch, model.feed(config, batch, seed, shards=chips)
