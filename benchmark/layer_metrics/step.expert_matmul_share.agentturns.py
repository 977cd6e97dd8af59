"""Share of the device's busy time in the traced seconds of the agent-turns
cell that the experts' GROUPED MATRIX PRODUCTS took: what
``step.expert_matmul_share.voiceturns`` reads, its reader (the events of the
Pallas grouped matmul ``gmm`` and of XLA's ``ragged-dot``, TWO a layer a
program here: a squared-ReLU expert has no gate matrix). One chip of 4 holds
a quarter of a layer's experts and a token chooses 22: 5.5 held experts a
token, each at the latent's width of 1024; a decode step of 128 rows sends 5.5
rows to each of 128 groups, under a tile. None where the run has no trace.
0.0 when the traced seconds hold no such product."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "lower", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.expert_matmul_share.voiceturns").read
