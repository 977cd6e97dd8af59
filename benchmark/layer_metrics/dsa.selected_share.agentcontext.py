"""How sparse attention was in the agent-context cell's window: of the keys
the dispatched queries could see, the share they attend to,
Δ``dsa_keys_selected`` / Δ``dsa_keys_visible`` of ``LLMEngine.counters()``
(summed on the host from every dispatched row's positions: ``t + 1`` and
``min(2048, t + 1)`` for a query at position ``t``, chunk rows and decode rows
alike). What the traffic's lengths imply: prompts uniform 3072-9216 read
about 50% (51.3 for the prompts alone, the decode rows pull it under; the
4096-12288 named first read 40%). None where the program has no such counters; 0.0 for a window
that dispatched no query."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "lower", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "dsa_keys_selected", "dsa_keys_visible")
    if d is None:
        return None
    selected, visible = d
    return 100.0 * selected / visible if visible > 0 else 0.0
