"""Model zoo (reference: bigdl/models/)."""

from bigdl_tpu.models import (
    alexnet, autoencoder, inception, lenet, loop_lm, ncf, resnet, rnn,
    textclassifier, vgg,
)
