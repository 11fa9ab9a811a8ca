"""reaplint: every REAP00x rule fires on its known-bad snippet, stays
quiet on the known-good twin, and the suppression comment is honoured
(and counted) only when it carries a reason.  The dynamic purity harness
must pass for every registered op — the runtime proof of REAP001."""
from pathlib import Path

from repro.analysis import check_source, check_sources

SRC_ROOT = Path(__file__).resolve().parents[1] / "src" / "repro"


def codes_and_lines(report):
    return [(d.code, d.line) for d in report.violations]


class TestReap001Purity:
    BAD = (
        "def inspect_gather(a, cfg, fp):\n"
        "    nnz_pattern = a.indptr[-1]\n"
        "    total = a.data.sum()\n"
        "    scale = float(nnz_pattern)\n"
        "    mags = abs(nnz_pattern)\n"
        "    return total + scale + mags\n")

    GOOD = (
        "def inspect_gather(a, cfg, fp):\n"
        "    rows = a.indptr[1:] - a.indptr[:-1]\n"
        "    cols = a.indices\n"
        "    out_dtype = a.data.dtype     # metadata of the buffer: pattern\n"
        "    return rows, cols, a.shape, out_dtype\n")

    def test_bad_fires_per_violation(self):
        report = check_source(self.BAD, "core/fixture.py")
        assert codes_and_lines(report) == [
            ("REAP001", 3), ("REAP001", 4), ("REAP001", 5)]
        assert "value buffer `.data`" in report.violations[0].message

    def test_good_is_clean(self):
        assert check_source(self.GOOD, "core/fixture.py").ok

    def test_hook_binding_scopes_unnamed_functions(self):
        # a function with a neutral name becomes inspector scope when an
        # OpSpec binds it to prepare=/inspect=/fingerprint=
        src = (
            "def build_thing(operands, cfg, **kw):\n"
            "    return operands[0].data.copy()\n"
            "spec = OpSpec(tag='t', prepare=build_thing)\n")
        report = check_source(src, "core/fixture.py")
        assert ("REAP001", 2) in codes_and_lines(report)


class TestReap002Registry:
    def test_missing_required_hooks(self):
        src = (
            "from repro.runtime.ops import OpSpec, register_op\n"
            "def _fp(o, cfg, *, chunked): pass\n"
            "register_op(OpSpec(tag='badop', fingerprint=_fp))\n")
        report = check_source(src, "core/fixture.py")
        assert [(d.code, d.line) for d in report.violations] == [
            ("REAP002", 3)]
        msg = report.violations[0].message
        assert "inspect" in msg and "execute_sync" in msg

    def test_two_executor_hooks_flagged(self):
        src = (
            "from repro.runtime.ops import OpSpec, register_op\n"
            "register_op(OpSpec(tag='twice', fingerprint=f, inspect=g,\n"
            "                   execute_sync=h, execute_chunked=k))\n")
        report = check_source(src, "core/fixture.py")
        assert [(d.code, d.line) for d in report.violations] == [
            ("REAP002", 2)]
        assert "exactly one executor" in report.violations[0].message

    def test_router_needs_no_other_hooks(self):
        src = (
            "from repro.runtime.ops import OpSpec, register_op\n"
            "def _route(o, cfg, routes, **kw): pass\n"
            "register_op(OpSpec(tag='alias', route=_route))\n")
        assert check_source(src, "core/fixture.py").ok

    def test_plan_type_must_be_dataclass(self):
        src = (
            "class NotAPlan:\n"
            "    pass\n"
            "spec = OpSpec(tag='op', fingerprint=f, inspect=g,\n"
            "              execute_sync=h, plan_types={'p': NotAPlan})\n")
        report = check_source(src, "core/fixture.py")
        assert [(d.code, d.line) for d in report.violations] == [
            ("REAP002", 4)]
        assert "NotAPlan" in report.violations[0].message

    def test_dataclass_plan_type_is_clean(self):
        src = (
            "import dataclasses\n"
            "@dataclasses.dataclass\n"
            "class Plan:\n"
            "    n: int\n"
            "spec = OpSpec(tag='op', fingerprint=f, inspect=g,\n"
            "              execute_sync=h, plan_types={'p': Plan})\n")
        assert check_source(src, "core/fixture.py").ok

    def test_op_tag_branch_in_generic_module(self):
        defs = ("spec = OpSpec(tag='fixture_op', fingerprint=f,\n"
                "              inspect=g, execute_sync=h)\n")
        api = ("def run(tag):\n"
               "    if tag == 'fixture_op':\n"
               "        return 1\n"
               "    table = {'fixture_op': 2}\n"
               "    return table\n")
        report = check_sources([("core/defs.py", defs),
                                ("repro/runtime/api.py", api)])
        assert [(d.code, d.line) for d in report.violations] == [
            ("REAP002", 2), ("REAP002", 4)]
        # the same branches outside the protected modules are fine
        report2 = check_sources([("core/defs.py", defs),
                                 ("repro/launch/serve.py", api)])
        assert report2.ok

    def test_undeclared_runstats_kwarg_in_generic_module(self):
        # REAP002d: RunStats fields are the declared schema — a new kwarg
        # in a protected runtime module must be added to RUNSTATS_FIELDS
        src = ("def run(hit):\n"
               "    return RunStats(cache_hit=hit, surprise=1,\n"
               "                    extra={'op': 'x'})\n")
        report = check_source(src, "repro/runtime/api.py")
        assert [(d.code, d.line) for d in report.violations] == [
            ("REAP002", 2)]
        assert "surprise" in report.violations[0].message
        assert "RUNSTATS_FIELDS" in report.violations[0].message
        # declared fields + the extra= passthrough are clean
        ok = ("def run(hit):\n"
              "    return RunStats(cache_hit=hit, store_hit=False,\n"
              "                    exec_cache_hit=None, extra={})\n")
        assert check_source(ok, "repro/runtime/api.py").ok
        # outside the protected modules the same call is unchecked
        assert check_source(src, "repro/launch/serve.py").ok

    def test_adhoc_stats_subscript_write_in_generic_module(self):
        src = ("def run(stats):\n"
               "    stats['made_up_key'] = 1\n"
               "    return stats\n")
        report = check_source(src, "repro/runtime/plan_cache.py")
        assert [(d.code, d.line) for d in report.violations] == [
            ("REAP002", 2)]
        assert "made_up_key" in report.violations[0].message
        # a declared field written through a stats mapping is fine, and
        # non-stats dicts are out of scope entirely
        ok = ("def run(stats, table):\n"
              "    stats['cache_hit'] = True\n"
              "    table['made_up_key'] = 1\n"
              "    return stats\n")
        assert check_source(ok, "repro/runtime/plan_cache.py").ok


class TestReap003Sync:
    BAD = (
        "def execute_sync_op(plan, operands, cfg):\n"
        "    out = jnp.dot(operands[0], operands[1])\n"
        "    host = np.asarray(out)\n"
        "    if out.sum() > 0:\n"
        "        host += 1\n"
        "    out.block_until_ready()\n"
        "    pulled = jax.device_get(out)\n"
        "    return np.asarray(out)\n")

    GOOD = (
        "def execute_sync_op(plan, operands, cfg):\n"
        "    out = jnp.dot(operands[0], operands[1])\n"
        "    if cfg.use_pallas:\n"
        "        out = out * 2\n"
        "    return np.asarray(out)[: plan.nnz]\n")

    def test_bad_fires_per_violation(self):
        report = check_source(self.BAD, "core/fixture.py")
        assert codes_and_lines(report) == [
            ("REAP003", 3), ("REAP003", 4),
            ("REAP003", 6), ("REAP003", 7)]

    def test_good_is_clean(self):
        # return-boundary np.asarray and config branches are allowed
        assert check_source(self.GOOD, "core/fixture.py").ok


class TestReap003SchedulerScope:
    """The serve scheduler's decode hot loop carries the sync-hygiene
    contract via SYNC_SCOPE_MODULES + HOT_LOOP_NAME_RE, without being an
    OpSpec executor."""

    HOT = (
        "def step(self):\n"
        "    logits = jnp.dot(self.w, self.x)\n"
        "    logits.block_until_ready()\n"
        "    return logits\n")

    def test_hot_loop_in_scheduler_module_is_scoped(self):
        report = check_source(self.HOT, "launch/scheduler.py")
        assert codes_and_lines(report) == [("REAP003", 3)]

    def test_same_code_outside_scope_module_is_clean(self):
        # neither an execute name nor a scoped module → no executor role
        assert check_source(self.HOT, "launch/other.py").ok

    def test_non_hot_names_in_scheduler_stay_unscoped(self):
        src = ("def submit(self, req):\n"
               "    x = jnp.asarray(req.prompt)\n"
               "    x.block_until_ready()\n")
        assert check_source(src, "launch/scheduler.py").ok

    def test_return_boundary_drain_is_allowed(self):
        src = ("def _decode_batch(self, tok):\n"
               "    logits = jnp.dot(self.w, tok)\n"
               "    return np.asarray(jnp.argmax(logits, axis=-1))\n")
        assert check_source(src, "launch/scheduler.py").ok

    def test_shipped_scheduler_is_clean(self):
        import pathlib
        import repro.launch.scheduler as sched
        path = pathlib.Path(sched.__file__)
        report = check_source(path.read_text(), "launch/scheduler.py")
        assert report.ok, [str(f) for f in report.findings]


class TestReap004Shapes:
    BAD = (
        "def spmm_execute(plan, vals):\n"
        "    return kernel(vals, c_nnz=plan.c_nnz)\n")

    GOOD = (
        "def spmm_execute(plan, vals):\n"
        "    cap = next_pow2(plan.c_nnz)\n"
        "    bt = min(128, cap)\n"
        "    return kernel(vals, c_nnz=cap, bt=bt)\n")

    JITTED = (
        "@functools.partial(jax.jit, static_argnames=('n_out',))\n"
        "def _block_execute(vals, n_out):\n"
        "    return seg(vals, num_segments=n_out + 1)\n")

    PERSISTENT = (
        "@persistent_jit(static_argnames=('n_out',))\n"
        "def _block_execute(vals, n_out):\n"
        "    return seg(vals, num_segments=n_out + 1)\n")

    def test_bad_fires(self):
        report = check_source(self.BAD, "core/fixture.py")
        assert codes_and_lines(report) == [("REAP004", 2)]
        assert "c_nnz" in report.violations[0].message

    def test_bucketed_and_derived_shapes_are_clean(self):
        assert check_source(self.GOOD, "core/fixture.py").ok

    def test_jitted_bodies_are_exempt(self):
        # inside jit the shapes are already static args; REAP004 is about
        # the launch sites that choose them
        assert check_source(self.JITTED, "core/fixture.py").ok

    def test_persistent_jit_bodies_are_exempt(self):
        # the exec-store wrapper lowers through jax.jit; its body has the
        # same traced-shape semantics, so the jit exemption applies
        assert check_source(self.PERSISTENT, "core/fixture.py").ok


class TestSuppressions:
    BAD_LINE = ("def inspect_w(w, cfg, fp):\n"
                "    return abs(w.sum())")

    def test_suppression_with_reason_counts(self):
        src = self.BAD_LINE + \
            "  # reaplint: disable=REAP001 pruning creates the pattern\n"
        report = check_source(src, "core/fixture.py")
        assert report.ok
        assert len(report.suppressed) == 1
        d = report.suppressed[0]
        assert d.code == "REAP001" and d.suppressed
        assert d.suppress_reason == "pruning creates the pattern"
        assert report.summary()["total_suppressions"] == 1

    def test_comment_block_above_also_applies(self):
        src = ("def inspect_w(w, cfg, fp):\n"
               "    # reaplint: disable=REAP001 magnitude pruning is the\n"
               "    # point of this inspector\n"
               "    return abs(w.sum())\n")
        report = check_source(src, "core/fixture.py")
        assert report.ok and len(report.suppressed) == 1

    def test_reason_is_mandatory(self):
        src = self.BAD_LINE + "  # reaplint: disable=REAP001\n"
        report = check_source(src, "core/fixture.py")
        assert not report.ok
        assert "reason is required" in report.violations[0].message

    def test_wrong_code_does_not_suppress(self):
        src = self.BAD_LINE + "  # reaplint: disable=REAP003 not my rule\n"
        report = check_source(src, "core/fixture.py")
        assert not report.ok and not report.suppressed


class TestRealTree:
    def test_src_repro_is_clean(self):
        """The acceptance gate: the shipped tree has zero unsuppressed
        violations (CI runs the same check via lint.yml)."""
        from repro.analysis import check_paths
        report = check_paths([SRC_ROOT])
        assert report.ok, report.format_text()
        # the audited exceptions are present and counted
        assert report.summary()["total_suppressions"] >= 1

    def test_parse_error_is_reported_not_crashed(self):
        report = check_source("def broken(:\n", "core/fixture.py")
        assert not report.ok
        assert report.violations[0].code == "REAP000"


class TestPurityHarness:
    def test_every_registered_op_replays_bit_identical(self):
        """Dynamic REAP001: perturbing values while holding the pattern
        fixed must leave every op's serialized plan bit-identical."""
        from repro.analysis.purity_check import run_purity_checks
        results = run_purity_checks(n=192)
        assert results, "no registered ops?"
        failed = {t: r["detail"] for t, r in results.items()
                  if not r["ok"]}
        assert not failed, failed
