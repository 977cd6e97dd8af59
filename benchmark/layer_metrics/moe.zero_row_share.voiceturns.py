"""Share of the rows the expert layers routed that chose a ZERO expert in the
voice-turns cell: Δ``expert_rows_zero`` / Δ``expert_rows_routed`` of
``LLMEngine.counters()`` over the window, every expert layer of every
program (a row is one of a token's twelve choices; 256 of the router's 768
outputs are the identity). Such a row costs no matrix work and no weight
byte, so the matrix work a token costs follows it: 33.3% in expectation on
seeded weights, level over seeds by the stratified bias. None where the
program has no such counters (a program from before zero experts); 0.0 for a
window that routed no row."""

from benchmark.program_readers import delta

DECLARATION = {"unit": "%", "better": "higher", "source": "program_counter",
               "layer": "model step", "moves": "serve_tokens_per_s"}


def read(run: dict):
    d = delta(run, "engine", "expert_rows_zero", "expert_rows_routed")
    if d is None:
        return None
    zero, routed = d
    return 100.0 * zero / routed if routed > 0 else 0.0
