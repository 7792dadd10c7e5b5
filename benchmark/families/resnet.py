"""ResNet (bottleneck) trained as ``examples/imagenet_resnet50.py:87-124``
trains it."""

from benchmark import flops
from benchmark.reference import resnet as reference  # noqa: F401


def build(config, mix, mesh, init_fn):
    import jax
    import jax.numpy as jnp
    import optax

    from tpucfn.models import ResNet, ResNetConfig
    from tpucfn.parallel import dense_rules
    from tpucfn.train import Trainer

    model, job = config["model"], config["job"]
    cfg = ResNetConfig(stage_sizes=tuple(model["stage_sizes"]),
                       num_classes=model["num_classes"], bottleneck=True,
                       width=model["width"],
                       dtype=jnp.dtype(job["compute_dtype"]),
                       param_dtype=jnp.dtype(job["param_dtype"]))
    net = ResNet(cfg)

    def loss_fn(params, mstate, batch, rng):
        logits, upd = net.apply({"params": params, **mstate}, batch["image"],
                                train=True, mutable=["batch_stats"])
        labels = optax.smooth_labels(
            jax.nn.one_hot(batch["label"], cfg.num_classes),
            job["label_smoothing"])
        loss = optax.softmax_cross_entropy(logits, labels).mean()
        acc = jnp.mean(jnp.argmax(logits, -1) == batch["label"])
        return loss, ({"accuracy": acc}, dict(upd))

    tx = optax.chain(
        optax.add_decayed_weights(job["weight_decay"]),
        optax.sgd(optax.warmup_cosine_decay_schedule(
            0.0, job["lr"], job["warmup_steps"], job["total_steps"]),
            momentum=job["momentum"], nesterov=True))
    trainer = Trainer(mesh, dense_rules(fsdp=False), loss_fn, tx, init_fn)
    return trainer, mix["shape"]["batch"]


def step_flops(model: dict, shape: dict) -> float:
    """Forward and backward: a backward pass costs two forward passes'."""
    return 3 * 2.0 * flops.resnet_forward_macs(model) * shape["batch"]
