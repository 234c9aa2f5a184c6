"""Documentation contracts (the CI docs job).

* Intra-repo markdown links in README.md / DESIGN.md / docs/*.md must
  resolve to real files — a rename or deletion breaks the build, not the
  reader.
* The support matrix embedded in ``docs/encodings.md`` must be exactly
  what ``repro.core.encoding.support_matrix_markdown()`` generates from
  the specs' own declarations, so the docs cannot drift from the code.
* The README quickstart and docs must reference only the live API
  surface (no resurrected ``engine.run`` calls).
"""

import pathlib
import re

import pytest

from repro.core import encoding

REPO = pathlib.Path(__file__).resolve().parents[1]
DOC_FILES = sorted(
    [REPO / "README.md", REPO / "DESIGN.md"] + list(REPO.glob("docs/*.md")))

# [text](target) — skip images ![..], external schemes and pure anchors
_LINK = re.compile(r"(?<!\!)\[[^\]]*\]\(([^)\s]+)\)")


def _intra_repo_links(path: pathlib.Path):
    for target in _LINK.findall(path.read_text()):
        if re.match(r"^[a-z][a-z0-9+.-]*:", target):   # http:, mailto:, ...
            continue
        if target.startswith("#"):
            continue
        yield target.split("#", 1)[0]


@pytest.mark.parametrize("doc", DOC_FILES, ids=lambda p: p.name)
def test_intra_repo_links_resolve(doc):
    missing = [t for t in _intra_repo_links(doc)
               if not (doc.parent / t).exists()]
    assert not missing, (
        f"{doc.relative_to(REPO)} links to missing files: {missing}")


def test_docs_exist():
    for p in (REPO / "docs" / "encodings.md", REPO / "docs" / "kernels.md",
              REPO / "docs" / "serving.md",
              REPO / "README.md", REPO / "DESIGN.md"):
        assert p.exists(), p


def test_kernels_guide_is_cross_linked():
    """docs/kernels.md (the kernels-path architecture guide) must be
    discoverable from both the README and the encoding guide, and is
    itself in DOC_FILES so its intra-repo links are drift-checked."""
    assert "docs/kernels.md" in (REPO / "README.md").read_text()
    assert "(kernels.md)" in (REPO / "docs" / "encodings.md").read_text()
    assert (REPO / "docs" / "kernels.md") in DOC_FILES


def test_kernels_guide_matches_code_surface():
    """The guide documents real symbols: every backticked module path and
    the schedule fields it tabulates must exist in the codebase."""
    text = (REPO / "docs" / "kernels.md").read_text()
    for rel in re.findall(r"`(src/[\w/]+\.py)`", text):
        assert (REPO / rel).exists(), f"docs/kernels.md names missing {rel}"
    from repro.core.encoding import KernelSchedule
    import dataclasses as _dc
    for field in _dc.fields(KernelSchedule):
        assert f"`{field.name}`" in text, (
            f"docs/kernels.md schedule table is missing {field.name}")


def test_kernels_guide_autotune_section():
    """§7 (autotuning & MXU lowering) documents the live tuning surface:
    every KernelConfig field, every MXU dtype, the cache env var, the
    perf-gate tolerance knob, and the gated bench rows — drift-checked
    against the code they describe."""
    text = (REPO / "docs" / "kernels.md").read_text()
    assert "## 7. Autotuning & MXU lowering" in text
    import dataclasses as _dc
    from repro.kernels.autotune import MXU_DTYPES, KernelConfig
    for field in _dc.fields(KernelConfig):
        assert f"`{field.name}`" in text, (
            f"docs/kernels.md §7 config table is missing {field.name}")
    for dt in MXU_DTYPES:
        assert f"`{dt}`" in text, (
            f"docs/kernels.md §7 is missing the {dt} lowering")
    assert "REPRO_AUTOTUNE_CACHE" in text      # cache location knob
    assert "REPRO_BENCH_TOL" in text           # perf-gate override knob
    from benchmarks.kernel_bench import GATE_ROWS
    for name in GATE_ROWS:
        assert name in text, (
            f"docs/kernels.md §7 is missing gated bench row {name}")


def test_bench_json_carries_tuned_rows():
    """The committed BENCH_kernels.json is the perf-gate baseline: it
    must carry the tuned rows the gate reads, min/std timing fields, and
    a uniform spikes_per_act convention (null == no spike schedule,
    never 0.0)."""
    import json as _json

    payload = _json.loads((REPO / "BENCH_kernels.json").read_text())
    rows = {r["name"]: r for r in payload["rows"]}
    from benchmarks.kernel_bench import GATE_ROWS
    for name in GATE_ROWS + ("dense_f32", "radix_bitserial_tuned"):
        assert name in rows, f"BENCH_kernels.json is missing row {name}"
    for r in rows.values():
        assert {"us_per_call", "us_mean", "us_std",
                "spikes_per_act"} <= set(r), r["name"]
    assert rows["dense_f32"]["spikes_per_act"] is None
    assert rows["dense_f32"]["tuned_config"] is None
    for name in ("radix_fused_tuned", "radix_bitserial_tuned"):
        assert rows[name]["tuned_config"] is not None, (
            f"{name} must record the winning KernelConfig")
        assert rows[name]["spikes_per_act"] is not None
    # the modeled-energy axis (docs/ppa.md §2): every row carries it,
    # null only for rows with no hardware analogue
    for r in rows.values():
        assert "modeled_energy_uj" in r, r["name"]
    assert rows["dense_f32"]["modeled_energy_uj"] is None
    assert rows["radix_fused"]["modeled_energy_uj"] is not None
    for enc_row in payload["encoding_latency"]:
        assert enc_row["modeled_energy_uj"] is not None, enc_row


def test_hyp_fallback_is_deterministic():
    """tests/_hyp.py's missing-hypothesis fallback must draw the same
    examples on every machine and run — the old behavior (skip) hid the
    property tests from slim containers; the new one runs them on
    fixed-seed draws."""
    from _hyp import fallback_given, fallback_settings, fallback_st

    seen = []

    @fallback_given(fallback_st.integers(0, 1000),
                    fallback_st.floats(0.0, 1.0),
                    flag=fallback_st.booleans())
    @fallback_settings(max_examples=7, deadline=None)
    def collect(a, b, flag):
        seen.append((a, b, flag))

    collect()
    first = list(seen)
    assert len(first) == 7
    seen.clear()
    collect()
    assert seen == first          # bit-identical replay


def test_hyp_fallback_reports_falsifying_example(capsys):
    from _hyp import fallback_given, fallback_st

    @fallback_given(fallback_st.integers(5, 9))
    def boom(v):
        raise AssertionError("nope")

    with pytest.raises(AssertionError):
        boom()
    assert "falsifying example" in capsys.readouterr().out


def test_every_skip_carries_a_reason():
    """Skip auditing: a bare ``pytest.mark.skip`` hides work without
    explanation.  Every skip/skipif in the suite must state its reason
    inline (the historical missing-hypothesis skips are gone — the _hyp
    fallback runs those tests deterministically instead)."""
    pat = re.compile(r"pytest\.mark\.skip(if)?\(")
    offenders = []
    for path in sorted((REPO / "tests").glob("*.py")):
        text = path.read_text()
        for mark in pat.finditer(text):
            window = text[mark.start():mark.start() + 200]
            if "reason=" not in window:
                line = text[:mark.start()].count("\n") + 1
                offenders.append(f"{path.name}:{line}")
    assert not offenders, f"skips without a stated reason: {offenders}"


def test_serving_guide_is_cross_linked():
    """docs/serving.md (the resilience guide) must be discoverable from
    both the README and DESIGN.md §3, and is itself in DOC_FILES so its
    intra-repo links are drift-checked."""
    assert "docs/serving.md" in (REPO / "README.md").read_text()
    assert "docs/serving.md" in (REPO / "DESIGN.md").read_text()
    assert (REPO / "docs" / "serving.md") in DOC_FILES


def test_serving_guide_matches_code_surface():
    """The guide documents real symbols: every backticked ``src/...py``
    path exists, the error taxonomy and ResilienceStats counters it
    tabulates are the live ones, and the counters all surface through a
    served model's stats()."""
    text = (REPO / "docs" / "serving.md").read_text()
    for rel in re.findall(r"`(src/[\w/]+\.py)`", text):
        assert (REPO / rel).exists(), f"docs/serving.md names missing {rel}"
    from repro.runtime import resilience
    import dataclasses as _dc
    for err in ("ServeError", "AdmissionError", "DeadlineExceeded",
                "RequestPoisoned"):
        assert hasattr(resilience, err), err
        assert f"`{err}`" in text, (
            f"docs/serving.md taxonomy table is missing {err}")
    for field in _dc.fields(resilience.ResilienceStats):
        assert f"`{field.name}`" in text, (
            f"docs/serving.md counter list is missing {field.name}")
    # the DESIGN.md failure-mode table names the same counters
    design = (REPO / "DESIGN.md").read_text()
    for field in _dc.fields(resilience.ResilienceStats):
        assert f"`{field.name}`" in design, (
            f"DESIGN.md failure-mode table is missing {field.name}")


def test_serving_guide_lists_the_trace_spans():
    """docs/serving.md's "Tracing a server" table holds exactly the spans
    of ``repro.runtime.trace.SPANS``, each with the arguments it
    carries."""
    from repro.runtime import trace

    text = (REPO / "docs" / "serving.md").read_text()
    section = text[text.index("## 7. Tracing a server"):]
    rows = re.findall(r"^\| `([\w.]+)` \|[^|]*\| (.*) \|$", section, re.M)
    assert {name: tuple(re.findall(r"`(\w+)`", args))
            for name, args in rows} == trace.SPANS


def test_ppa_guide_is_cross_linked():
    """docs/ppa.md (the planner guide) must be discoverable from the
    README and DESIGN.md §9, and is itself in DOC_FILES so its
    intra-repo links are drift-checked."""
    assert "docs/ppa.md" in (REPO / "README.md").read_text()
    design = (REPO / "DESIGN.md").read_text()
    assert "## §9 PPA planner" in design and "docs/ppa.md" in design
    assert (REPO / "docs" / "ppa.md") in DOC_FILES


def test_ppa_guide_matches_code_surface():
    """The guide documents real symbols: every backticked ``src/...py``
    path exists, the stats keys it promises are the provider's, and the
    constraint kwargs it names are autoconfigure's signature."""
    text = (REPO / "docs" / "ppa.md").read_text()
    for rel in re.findall(r"`(src/[\w/]+\.py)`", text):
        assert (REPO / rel).exists(), f"docs/ppa.md names missing {rel}"
    import inspect
    from repro.ppa import search
    params = inspect.signature(search.autoconfigure).parameters
    for kwarg in ("accuracy_floor", "latency_slo_us", "energy_budget_uj",
                  "t_range", "units", "objective", "labels"):
        assert kwarg in params, kwarg
        if kwarg in ("accuracy_floor", "latency_slo_us",
                     "energy_budget_uj"):
            assert f"`{kwarg}" in text, (
                f"docs/ppa.md constraint list is missing {kwarg}")
    # the stats()["ppa"] keys the surface table promises
    for key in ("latency_us", "energy_uj", "power_w", "area_klut",
                "area_kff"):
        assert f"`{key}`" in text, f"docs/ppa.md stats keys missing {key}"
    import dataclasses as _dc
    from repro.ppa.model import PPAReport
    report_fields = {f.name for f in _dc.fields(PPAReport)}
    assert {"latency_us", "energy_uj", "power_w", "klut", "kff",
            "effective_steps"} <= report_fields


def test_support_matrix_matches_spec_declarations():
    """docs/encodings.md support matrix == the generated one, verbatim."""
    text = (REPO / "docs" / "encodings.md").read_text()
    m = re.search(r"<!-- support-matrix:begin -->\n(.*?)\n"
                  r"<!-- support-matrix:end -->", text, re.S)
    assert m, "support-matrix markers missing from docs/encodings.md"
    assert m.group(1).strip() == encoding.support_matrix_markdown().strip(), (
        "docs/encodings.md support matrix drifted from the specs' declared "
        "capabilities — regenerate it with "
        "repro.core.encoding.support_matrix_markdown()")


def test_support_matrix_covers_every_spec():
    names = {cls.name for cls in encoding.SPECS}
    assert names == {"radix", "rate", "ttfs", "phase"}
    rows = encoding.support_matrix()
    assert [r["name"] for r in rows] == [cls.name for cls in encoding.SPECS]
    for row in rows:
        cls = dict(zip([c.name for c in encoding.SPECS],
                       encoding.SPECS))[row["name"]]
        assert row["backends"] == cls.backends
        assert row["kernel_dataflows"] == cls.kernel_dataflows
        assert row["pool_modes"] == cls.pool_modes


def test_no_stale_engine_run_recommendation():
    """engine.run survives only as a deprecation shim; user-facing docs
    must not tell anyone to call it (mentioning the shim status is fine)."""
    for doc in DOC_FILES:
        for line in doc.read_text().splitlines():
            if "engine.run(" in line and "deprecat" not in line.lower():
                # allowed only in the DESIGN.md migration table's OLD column
                assert "| `engine.run(" in line.strip(), (
                    f"{doc.name}: stale engine.run reference: {line!r}")


def test_lm_guide_is_cross_linked():
    """docs/lm.md (the radix-LM serving guide) must be discoverable from
    the README and the kernels guide (whose autotune table the LM path
    rides), and is itself in DOC_FILES so its intra-repo links are
    drift-checked."""
    assert "docs/lm.md" in (REPO / "README.md").read_text()
    assert "(lm.md)" in (REPO / "docs" / "kernels.md").read_text()
    assert "(kernels.md)" in (REPO / "docs" / "lm.md").read_text()
    assert (REPO / "docs" / "lm.md") in DOC_FILES


def test_lm_guide_matches_code_surface():
    """The guide documents real symbols: every backticked ``src/...py``
    path exists, the serving ArchConfig knobs it explains are live
    fields, and the stats counters it promises are what an LMExecutable
    actually reports."""
    text = (REPO / "docs" / "lm.md").read_text()
    for rel in re.findall(r"`(src/[\w/]+\.py)`", text):
        assert (REPO / rel).exists(), f"docs/lm.md names missing {rel}"
    import dataclasses as _dc
    from repro.lm.config import ArchConfig
    fields = {f.name for f in _dc.fields(ArchConfig)}
    for knob in ("use_kernel", "kernel_autotune", "kernel_dataflow",
                 "radix_attn", "radix_kv_pack", "packed_attn"):
        assert knob in fields, knob
        assert f"`cfg.{knob}`" in text or f"`{knob}`" in text, (
            f"docs/lm.md is missing the {knob} serving knob")
    # the packed-attention section names the live kernel module and
    # both docs explain the plane algebra it implements
    assert "src/repro/kernels/radix_attn.py" in text
    ktext = (REPO / "docs" / "kernels.md").read_text()
    assert "radix_attn" in ktext, (
        "docs/kernels.md is missing the packed decode-attention kernel")
    from repro.kernels.radix_attn import Q_BITS
    assert f"Q_BITS = {Q_BITS}" in text or f"Q_BITS ({Q_BITS}" in text or \
        f"`Q_BITS` = {Q_BITS}" in text, (
        "docs/lm.md must state the query-quantization width Q_BITS")
    # the plan-cache counters §3 promises are the LMPlanCache's
    from repro.core.engine import PlanCacheStats
    stats_keys = set(PlanCacheStats().as_dict())
    for key in ("compiles", "padded_rows"):
        assert key in stats_keys, key
        assert f"`{key}`" in text, f"docs/lm.md stats keys missing {key}"
    assert "REPRO_LM_AGREE_FLOOR" in text     # accuracy-gate floor knob
    assert "REPRO_BENCH_TOL" in text          # shared tolerance knob


def test_bench_lm_json_structure():
    """The committed BENCH_lm.json is the lm-accuracy-gate baseline: it
    must carry the serving rows (prefill per bucket + decode, tok/s),
    the decode_attn packed-vs-float rows the lm_bench --check ratio
    gate re-measures, the zero-recompile cache proof, and the accuracy
    sweep the lm_radix_accuracy --check gate reads."""
    import json as _json

    payload = _json.loads((REPO / "BENCH_lm.json").read_text())
    assert payload["bench"] == "lm"
    phases = {}
    for r in payload["serving"]:
        phases.setdefault(r["phase"], []).append(r)
        assert r["tok_s"] > 0, r
    assert set(phases) == {"prefill", "decode"}
    assert len(phases["prefill"]) == len(payload["config"]["seq_buckets"])
    assert payload["cache"]["steady_state_recompiles"] == 0
    attn = {r["row"]: r for r in payload["decode_attn"]}
    assert set(attn) == {"decode_attn_packed", "decode_attn_float"}
    for r in attn.values():
        assert r["us_per_token"] > 0, r
    from benchmarks.lm_radix_accuracy import T_SWEEP
    acc = {r["T"]: r for r in payload["accuracy"]}
    assert set(acc) == set(T_SWEEP)
    errs = [acc[T]["logit_rel_err"] for T in sorted(acc)]
    assert all(b <= a for a, b in zip(errs, errs[1:])), errs
    for r in acc.values():
        assert 0.0 <= r["argmax_agree"] <= 1.0
