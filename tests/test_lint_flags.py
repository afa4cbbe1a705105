"""tools/lint_flags.py — the trace-cache key completeness meta-lint.

Tier-1 wiring: the clean-tree check IS the CI gate (a new uncached
trace-affecting read fails this suite), and the planted-defect check
proves the scanner actually sees new code rather than vacuously
passing.
"""
import os
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "tools"))

import lint_flags  # noqa: E402  (tools/lint_flags.py)


def test_keyed_names_cover_the_long_standing_set():
    keyed = lint_flags.keyed_names()
    # spot-check both kinds: flags and env vars folded into the one
    # tuple both keys carry (_trace_key_items)
    for name in ("FLAGS.check_nan_inf", "FLAGS.stability_guard",
                 "FLAGS.use_custom_kernels", "PT_STABILITY_POLICY",
                 "PT_RECOMPUTE", "PT_COMPILER_OPTIONS",
                 "PT_FORCE_KERNEL", "PT_FORCE_COMPOSED"):
        assert name in keyed, name


def test_current_tree_is_clean(capsys):
    assert lint_flags.run() == lint_flags.EXIT_CLEAN
    assert "clean" in capsys.readouterr().out


def test_trace_affecting_knobs_are_all_keyed():
    # the tuning catalog's trace_affecting metadata and the engine key
    # must not drift apart
    assert lint_flags.knob_gaps(lint_flags.keyed_names()) == []


def test_planted_uncached_env_read_is_flagged(tmp_path, capsys):
    planted = tmp_path / "new_kernel.py"
    planted.write_text(textwrap.dedent("""\
        import os
        from paddle_tpu.core.flags import FLAGS

        def pick_variant(q):
            # trace-time branch on an env var nobody keys
            if os.environ.get("PT_BOGUS_TRACE_KNOB"):
                return "wide"
            if FLAGS.check_nan_inf:     # keyed: must NOT be flagged
                return "checked"
            if getattr(FLAGS, "stability_guard", False):  # keyed too
                return "guarded"
            return "narrow"
    """))
    rc = lint_flags.run([str(planted)])
    out = capsys.readouterr().out
    assert rc == lint_flags.EXIT_FINDINGS
    assert "PT_BOGUS_TRACE_KNOB" in out
    assert "check_nan_inf" not in out
    assert "stability_guard" not in out


def test_planted_unkeyed_flag_read_is_flagged(tmp_path, capsys):
    planted = tmp_path / "new_pass.py"
    planted.write_text(
        "from paddle_tpu.core.flags import FLAGS\n"
        "def trace_hook():\n"
        "    return FLAGS.some_new_trace_knob\n")
    rc = lint_flags.run([str(planted)])
    assert rc == lint_flags.EXIT_FINDINGS
    assert "FLAGS.some_new_trace_knob" in capsys.readouterr().out


def test_subscript_and_getenv_forms_are_seen(tmp_path, capsys):
    planted = tmp_path / "forms.py"
    planted.write_text(
        "import os\n"
        "a = os.environ['PT_FORM_SUBSCRIPT']\n"
        "b = os.getenv('PT_FORM_GETENV')\n")
    rc = lint_flags.run([str(planted)])
    out = capsys.readouterr().out
    assert rc == lint_flags.EXIT_FINDINGS
    assert "PT_FORM_SUBSCRIPT" in out and "PT_FORM_GETENV" in out


def test_cli_exit_codes(tmp_path):
    assert lint_flags.main([]) == lint_flags.EXIT_CLEAN
    assert lint_flags.main(
        ["--extra", str(tmp_path / "missing.py")]) == lint_flags.EXIT_USAGE


def test_allowlist_entries_all_carry_justifications():
    for name, why in lint_flags.ALLOWLIST.items():
        assert why and len(why) > 10, name
