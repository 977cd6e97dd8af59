"""Of what the window's decode steps HAD TO MOVE, the share that was state and
not weights, in the agent-turns cell: Δ``state_bytes_stepped`` of
``LLMEngine.counters()`` (the bytes of sequence entries that decode steps, and
the steps a chunk program carries, read AND wrote: twice a live row's entries
over the five Mamba layers, 42.6 MB a row a step, reckoned by the engine from
its own planes' shapes) over that plus Δ``decode_steps_dispatched`` x the
architecture's ``counts.decode_weight_bytes`` at the window's mean live
streams (Δ``decode_tokens_emitted`` / Δ``decode_steps_dispatched``). A stream
costs this model 21 MB and a token 1 KB, so what a chip's memory buys is
streams; but every step reads and writes every live stream's state while the
experts' bytes stand still: 5.45 GB beside 9.0 GB at 128 streams, 37%. Lower
is better: the same streams served by moving less state (a state in a
narrower type, a step that touches fewer layers) show here under the same
name. K and V rows (0.2 GB a step) are in neither term.

None where the program has no such counter (a program from before it). 0.0
for a window that dispatched no step or whose stack keeps no state a
sequence."""

from benchmark import architecture
from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "state_bytes_stepped",
              "decode_steps_dispatched", "decode_tokens_emitted")
    if d is None:
        return None
    state, steps, tokens = d
    if steps <= 0 or state <= 0:
        return 0.0
    conf = run["config"]
    weights = steps * architecture.part(conf, "counts").decode_weight_bytes(
        conf, run["weight_bytes_per_param"], tokens / steps)
    return 100.0 * state / (state + weights)
