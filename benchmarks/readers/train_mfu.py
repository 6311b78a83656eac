"""Model FLOP/s utilisation of the training window, in percent: trained
tokens per second per chip x the operations forward and backward require
per token (the family's ``train_flops_per_token``; recomputed operations
are not counted) over the chip's published bf16 peak."""


def read(run: dict, args: dict):
    tok_s = run["values"].get("train_tok_s")
    if tok_s is None or not run.get("peaks"):
        return None
    flops = run["family"].train_flops_per_token(run["sizes"], run["seq_len"])
    return 100.0 * tok_s * flops / run["peaks"]["bf16_flops_per_s"]
