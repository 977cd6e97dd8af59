"""Model FLOP/s utilisation of the window: tokens per second per chip times
the operations one token needs forward and backward (the architecture's
``counts.py``: 6 per multiplied parameter plus causal attention, no
recompute), over the chip's published bf16 peak. End-to-end arithmetic on the host's clock, not a
kernel's roofline share."""

from benchmark import architecture

DECLARATION = {"unit": "%", "better": "higher", "source": "host_clock",
               "layer": "trainer loop", "moves": "train_tokens_per_s_chip"}


def read(run: dict):
    train, peaks = run.get("train"), run.get("peaks")
    if train is None or peaks is None:
        return None
    per_token = architecture.part(
        run["config"], "counts").train_flops_per_token(
            run["config"], train["seq_len"])
    return 100.0 * train["tokens_per_s_chip"] * per_token \
        / peaks["bf16_flops"]
