"""Model zoo: the reference's headline benchmark families re-implemented
as idiomatic flax modules (bfloat16 compute, fp32 state, NHWC), and seven
language models trained through ``hvd.make_train_step`` (``docs/models.md``):
``transformer.TransformerLM``, ``qwen3_next.Qwen3NextLM``,
``lfm2_moe.Lfm2MoeLM``, ``xing4.Xing4LM``, ``keye_vl.KeyeVLLM``,
``nemotron_h.NemotronHLM`` and ``ling.LingLM``."""

from __future__ import annotations


def get_model(name: str, **kwargs):
    """Factory keyed by the benchmark names the reference's scripts use
    (``resnet50``, ``vgg16``, ``inception3``, ...)."""
    name = name.lower().replace("-", "").replace("_", "")
    from . import inception, resnet, vgg

    zoo = {
        "resnet18": resnet.ResNet18,
        "resnet34": resnet.ResNet34,
        "resnet50": resnet.ResNet50,
        "resnet101": resnet.ResNet101,
        "resnet152": resnet.ResNet152,
        "vgg11": vgg.VGG11,
        "vgg16": vgg.VGG16,
        "vgg19": vgg.VGG19,
        "inception3": inception.InceptionV3,
        "inceptionv3": inception.InceptionV3,
    }
    if name not in zoo:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(zoo)}"
        )
    return zoo[name](**kwargs)
