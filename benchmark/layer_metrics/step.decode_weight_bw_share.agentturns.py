"""How close one decode step of the agent-turns cell comes to the time its
weights alone take to cross the memory bus: what
``step.decode_weight_bw_share.mixedlength`` reads, its reader (a step is ONE
execution of ``paged_decode_attention`` inside a decode-ONLY program: the
configuration's ``layer_types_held`` names one full-attention layer; the steps
that ride a chunk program are another module and are not read), with this
architecture's ``counts.decode_weight_bytes``: the five mixers, the attention,
five routers, latent projections and shared experts, the final norm and the
head (1.98 GB) and of the 7.05 GB of held experts those some of the window's
mean live streams are expected to choose (99.6% at 128). The states (5.45 GB a
128-stream step, read and written) and the K and V rows are left out: the
share says how far the step stands from its weights alone, and
``step.state_bytes_share.agentturns`` says what it moves beside them. None
where the run has no trace; 0.0 when the traced seconds hold no decode
dispatch."""

from benchmark.manifest import load_layer_metric

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "model step", "moves": "serve_tokens_per_s"}

read = load_layer_metric("step.decode_weight_bw_share.mixedlength").read
