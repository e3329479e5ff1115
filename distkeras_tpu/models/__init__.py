from .zoo import (mnist_mlp, mnist_convnet, cifar10_convnet, higgs_mlp,
                  transformer_lm, hybrid_lm)

__all__ = ["mnist_mlp", "mnist_convnet", "cifar10_convnet", "higgs_mlp",
           "transformer_lm", "hybrid_lm"]
