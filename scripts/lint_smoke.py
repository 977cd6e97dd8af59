#!/usr/bin/env python
"""Static-analysis gate for smoke.sh: ``kftpu lint`` over the whole tree.

Fails on ANY finding not matched by the checked-in baseline
(.kftpu-lint-baseline.json) — pre-existing debt is baselined with a
justification, new findings block. Also self-checks the analyzer the way
the acceptance criteria demand: each rule family must still catch its
seeded regression — the PR-4 per-round ``jnp.asarray(self._table)``
upload (D103), a dropped router lock acquisition (C301), a de-donated
decode carry (S401), an exception-path page leak (R501), an inverted
router lock pair (R503), a fire-and-forget trainer checkpoint save
(R504), a weak-type scalar riding into the decode dispatch (F602),
a fresh tuple in its static num_steps position (F604), a renamed
autoscaler-scraped series (X701, linted under the full package Program
so the cross-component table sees the real producers), a typoed
header literal (X703), and the ISSUE-20 liveness family: a router
metrics probe stripped of its timeout (T801), an inline sleep-retry
loop (T802), the kv-migrate join dropped from KVTier.close (T803), a
queue get under the router lock (T804), and the relay's derived
``timeout=remaining`` hardened to a literal (T805) — so a rule that
silently stops firing fails the gate too, not just the test suite.

Prints one JSON object; ``"lint_smoke": "ok"`` is the pass marker
smoke.sh greps for. Findings render as ``file:line:col`` so they are
clickable in CI logs; ``wall_time_s`` tracks the whole-program scan's
cost (ISSUE 8: parse-once + shared per-module structures made the
self-scan faster despite the added F-family and cross-module
resolution).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from kubeflow_tpu.analysis import Baseline, find_baseline, lint_source, run_lint  # noqa: E402
from kubeflow_tpu.analysis import core as _core  # noqa: E402

SCAN = ["kubeflow_tpu", "scripts", "bench.py", "bench_serve.py"]


def _lint_with_program(relpath: str, src: str):
    """Lint ONE (possibly mutated) source under the full package-wide
    Program — the X-family cross-component rules need the real producers
    and consumers on the other side of each contract visible, which
    ``lint_source``'s standalone module cannot provide."""
    mods = []
    for path in _core.iter_py_files(SCAN):
        rel = os.path.relpath(os.path.abspath(path), REPO).replace(
            os.sep, "/")
        if rel == relpath:
            mods.append(_core.Module(relpath, src))
        else:
            try:
                mods.append(_core.load_module(path, rel))
            except (OSError, SyntaxError, ValueError):
                continue
    _core.Program(mods)
    target = next(m for m in mods if m.relpath == relpath)
    return _core.lint_module(target)


def _seeded_regressions() -> list[str]:
    """Mutate engine/router source in memory and check each rule family
    still fires exactly once. Returns a list of failure descriptions."""
    fails: list[str] = []

    def new_findings(path: str, edits, rule: str, needle: str) -> None:
        """``edits``: one (old, new) pair or a list of them — some seeds
        (the R503 lock-order inversion) need both an __init__ line and
        the inverted methods."""
        if isinstance(edits, tuple):
            edits = [edits]
        with open(os.path.join(REPO, path)) as f:
            src = f.read()
        mut = src
        for old, new in edits:
            nxt = mut.replace(old, new, 1)
            if nxt == mut:
                fails.append(
                    f"{rule}: mutation anchor not found in {path}")
                return
            mut = nxt
        before = {f.fingerprint for f in lint_source(src, path)}
        fresh = [f for f in lint_source(mut, path)
                 if f.fingerprint not in before]
        if len(fresh) != 1 or fresh[0].rule != rule \
                or needle not in fresh[0].message:
            fails.append(
                f"{rule}: seeded regression in {path} produced "
                f"{[f.render() for f in fresh]!r}, expected exactly one "
                f"{rule} mentioning {needle!r}")

    # Family A: the PR-4 bug — full page-table re-upload per decode round.
    new_findings(
        "kubeflow_tpu/serve/engine.py",
        ("        self._sync_decode_state()\n",
         "        self._sync_decode_state()\n"
         "        table = jnp.asarray(self._table)\n"),
        "D103", "self._table")
    # Family B: drop one router lock acquisition.
    new_findings(
        "kubeflow_tpu/serve/router.py",
        ("    def note_activity(self) -> None:\n        with self._lock:\n",
         "    def note_activity(self) -> None:\n        if True:\n"),
        "C301", "_last_activity")
    # Family S: drop the decode dispatch's carry donation (2x HBM).
    new_findings(
        "kubeflow_tpu/serve/engine.py",
        ("            _paged_decode_fn, static_argnums=(5, 6),\n"
         "            donate_argnums=(1, 2, 3))",
         "            _paged_decode_fn, static_argnums=(5, 6))"),
        "S401", "self._paged_decode_n")
    # Family R: a raise-capable call between page alloc and the ownership
    # recording — the exception path leaks the pages.
    new_findings(
        "kubeflow_tpu/serve/engine.py",
        ("owner=self._slot_owner(slot_idx))\n",
         "owner=self._slot_owner(slot_idx))\n"
         "            self._refresh_pool_gauge()\n"),
        "R501", "_ensure_pages")
    # Family R: a second router lock acquired in both orders (the cycle
    # KFTPU_SANITIZE=lockorder would catch at runtime).
    new_findings(
        "kubeflow_tpu/serve/router.py",
        [("        self._lock = threading.Lock()\n",
          "        self._lock = threading.Lock()\n"
          "        self._aux_lock = threading.Lock()\n"),
         ("    def note_activity(self) -> None:\n",
          "    def _seed_ab(self):\n"
          "        with self._lock:\n"
          "            with self._aux_lock:\n"
          "                pass\n\n"
          "    def _seed_ba(self):\n"
          "        with self._aux_lock:\n"
          "            with self._lock:\n"
          "                pass\n\n"
          "    def note_activity(self) -> None:\n")],
        "R503", "lock-order inversion")
    # Family R: a fire-and-forget checkpoint save on the training loop —
    # the acceptance bool dropped, no exception handling (the exact
    # Trainer.save bug ISSUE 9 fixed; a broken checkpoint store would
    # vanish silently instead of raising the save-failure alarm).
    new_findings(
        "kubeflow_tpu/train/trainer.py",
        ("        start = self.try_resume()\n",
         "        start = self.try_resume()\n"
         "        self.ckpt.save(0, self.task.state)\n"),
        "R504", "self.ckpt.save")
    # Family F: a weak-typed Python scalar in the decode dispatch (a fresh
    # compile-cache entry per scalar source) — the cycle
    # KFTPU_SANITIZE=recompile would catch at runtime. The dispatch runs
    # the fused RMSNorm Pallas kernel inside it (layers.rmsnorm): exactly
    # the steady-state recompile the warmed-fused-step sanitizer test pins
    # to zero.
    _DECODE_CALL = (
        "            out, self.cache, st, tbl = self._paged_decode_n(\n"
        "                self.params, self.cache, self._dstate.arrays,\n"
        "                self._dstate.table, key, k_steps, mode)")
    new_findings(
        "kubeflow_tpu/serve/engine.py",
        (_DECODE_CALL,
         _DECODE_CALL.replace(" key, k_steps, mode)", " 0.5, k_steps, mode)")),
        "F602", "self._paged_decode_n")
    # Family F: a per-call tuple in the dispatch's STATIC num_steps
    # position — hashed by value each call, a retrace per dispatch.
    new_findings(
        "kubeflow_tpu/serve/engine.py",
        (_DECODE_CALL,
         _DECODE_CALL.replace(" key, k_steps, mode)",
                              " key, (k_steps,), mode)")),
        "F604", "self._paged_decode_n")

    # Family T: strip the scrape probe's timeout — the exact unbounded
    # urlopen class that wedged a router behind a SIGKILLed replica.
    new_findings(
        "kubeflow_tpu/serve/router.py",
        ('with urllib.request.urlopen(url + "/metrics",\n'
         '                                            timeout=1.0) as r:',
         'with urllib.request.urlopen(url + "/metrics") as r:'),
        "T801", "urllib.request.urlopen")
    # Family T: an inline sleep-and-swallow retry loop instead of the
    # blessed serve/retry.py::call_with_retry helper.
    new_findings(
        "kubeflow_tpu/serve/handoff.py",
        [("import json\n", "import json\nimport time\n"),
         ("    def validate(self) -> None:\n"
          "        if self.kv_k.shape != self.kv_v.shape:\n",
          "    def validate(self) -> None:\n"
          "        attempt = 0\n"
          "        while attempt < 5:\n"
          "            try:\n"
          "                json.loads(\"{}\")\n"
          "                break\n"
          "            except ValueError:\n"
          "                attempt += 1\n"
          "                time.sleep(0.05)\n"
          "        if self.kv_k.shape != self.kv_v.shape:\n")],
        "T802", "call_with_retry")
    # Family T: drop the kv-migrate join from KVTier.close — the thread
    # outlives the tier (the leak KFTPU_SANITIZE=threads catches live).
    new_findings(
        "kubeflow_tpu/serve/kvtier.py",
        ("            self._queue.put(None)\n"
         "            self._thread.join(timeout=5.0)\n"
         "            self._thread = None\n",
         "            self._queue.put(None)\n"
         "            self._thread = None\n"),
        "T803", "_thread")
    # Family T: an unbounded queue get while holding the router lock —
    # the attr-based wait C302's fixed call set misses.
    new_findings(
        "kubeflow_tpu/serve/router.py",
        ("    def note_activity(self) -> None:\n",
         "    def _drain_locked(self):\n"
         "        with self._lock:\n"
         "            return self._retire_q.get()\n\n"
         "    def note_activity(self) -> None:\n"),
        "T804", "while holding")

    def new_findings_prog(path: str, old: str, new: str, rule: str,
                          needle: str) -> None:
        """The X-family variant: lint the mutated module under the FULL
        package Program (cross-component contracts need both sides)."""
        with open(os.path.join(REPO, path)) as f:
            src = f.read()
        mut = src.replace(old, new, 1)
        if mut == src:
            fails.append(f"{rule}: mutation anchor not found in {path}")
            return
        before = {f.fingerprint for f in _lint_with_program(path, src)}
        fresh = [f for f in _lint_with_program(path, mut)
                 if f.fingerprint not in before]
        if len(fresh) != 1 or fresh[0].rule != rule \
                or needle not in fresh[0].message:
            fails.append(
                f"{rule}: seeded regression in {path} produced "
                f"{[f.render() for f in fresh]!r}, expected exactly one "
                f"{rule} mentioning {needle!r}")

    # Family X: rename one scraped series in the autoscaler probe — the
    # engine still produces the old name, the probe now matches nothing
    # (the silent-HOLD drift class ISSUE 10 exists to kill).
    new_findings_prog(
        "kubeflow_tpu/serve/isvc_controller.py",
        '"kftpu_serving_requests_total"',
        '"kftpu_serving_requests_totals"',
        "X701", "kftpu_serving_requests_totals")
    # Family X: typo one header literal on the model server's read side —
    # nothing sets the misspelled header, so the QoS class silently
    # defaults for every request.
    new_findings_prog(
        "kubeflow_tpu/serve/server.py",
        "raw = self.headers.get(QOS_HEADER) or body.get(\"qos\")",
        "raw = self.headers.get(\"X-Kftpu-Qoss\") or body.get(\"qos\")",
        "X703", "X-Kftpu-Qoss")
    # Family T: the relay forwards the caller's remaining budget today —
    # harden it to a literal and the handler scope that READS the
    # deadline header (resolved through the Program-wide header table)
    # now ignores it.
    new_findings_prog(
        "kubeflow_tpu/serve/router.py",
        "resp = urllib.request.urlopen(req, timeout=remaining)",
        "resp = urllib.request.urlopen(req, timeout=30.0)",
        "T805", "timeout=30.0")
    return fails


def main() -> int:
    os.chdir(REPO)
    baseline_path = find_baseline(SCAN)
    baseline = Baseline.load(baseline_path) if baseline_path else None
    result = run_lint(SCAN, baseline=baseline, root=REPO)
    seeded = _seeded_regressions()
    ok = result.ok and not seeded
    print(json.dumps({
        "lint_smoke": "ok" if ok else "FAIL",
        "files_scanned": result.files_scanned,
        "wall_time_s": round(result.wall_time_s, 3),
        "findings": [f.render() for f in result.errors + result.new],
        "baselined": len(result.baselined),
        "baseline": (os.path.relpath(baseline_path, REPO)
                     if baseline_path else None),
        "seeded_regression_failures": seeded,
    }, indent=2))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
