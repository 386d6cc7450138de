"""Family ``decoder_lm``: a decoder-only transformer language model.
FLOPs of one training step from the configuration's sizes."""
from flops import transformer_layer_forward


def train_step_flops(config, batch):
    """FLOPs of one training step of the decoder-only model on ``batch``
    sequences of the configured length: three times the forward products
    (the embedding is a lookup and counts nothing)."""
    t = config["input"]["length"]
    e, f = config["n_embd"], config["n_inner"]
    fwd = config["n_layer"] * transformer_layer_forward(
        batch, t, e, f, config["n_head"])
    fwd += 2.0 * batch * t * e * config["vocab_size"]
    return 3.0 * fwd
