"""Model FLOPs the algorithm needs, computed from shapes.  A multiply-add is
two operations; the backward pass costs twice the forward; nothing is
counted for recomputation, and an embedding look-up multiplies nothing."""


def transformer_layer_forward_flops(tokens, seq, hidden, intermediate):
    """One transformer layer, forward, for ``tokens`` tokens in sequences of
    ``seq``: QKV, attention output, the two MLP matmuls, and the two
    attention matmuls (scores and context, each 2*seq*hidden per token,
    the full square: bidirectional, or causal computed densely)."""
    weights = 4 * hidden * hidden + 2 * hidden * intermediate
    return tokens * (2 * weights + 4 * seq * hidden)


def bert_pretrain_flops_per_sequence(hidden, layers, intermediate, vocab, seq,
                                     max_predictions):
    """Forward + backward of one BERT pretraining sequence: the encoder on
    all ``seq`` positions, the pooler on one, the MLM transform and the
    tied vocabulary projection on the ``max_predictions`` gathered
    positions only, the NSP head on one."""
    enc = layers * transformer_layer_forward_flops(seq, seq, hidden,
                                                   intermediate)
    pooler = 2 * hidden * hidden
    head = max_predictions * (2 * hidden * hidden + 2 * hidden * vocab)
    nsp = 2 * hidden * 2
    return 3 * (enc + pooler + head + nsp)

