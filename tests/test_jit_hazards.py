"""Tier-1 for the recompile & transfer hazard pair: every ndsjit rule
(nds_tpu/analysis/jit_hazards.py) fires on its positive fixture and
stays silent on its negative twin (tests/fixtures/jit_hazards/), the
shared suppression grammar (waive[] / disable=) holds under the ndsjit
marker, and the runtime sanitizer (nds_tpu/analysis/jitsan.py) catches
a SEEDED post-warmup recompile and a hidden ``.item()`` on a private
Sanitizer — the proof the detector would catch the real thing."""

import pathlib

import pytest

from nds_tpu.analysis import jit_hazards

FIXTURES = (pathlib.Path(__file__).parent / "fixtures"
            / "jit_hazards")


def _scan(fixture: str, synth_path: str):
    """Feed one fixture to the scanner under a synthetic nds_tpu path
    so the path-scoped rules apply to it."""
    src = (FIXTURES / fixture).read_text()
    return jit_hazards.scan_sources({synth_path: src})


def _hits(res, rule: str):
    return [v for v in res.violations if v.rule == rule]


class TestRuleFixtures:
    # (rule, positive fixture, negative fixture, synthetic path,
    #  minimum positive findings)
    CASES = [
        ("NDSJ301", "traced_leak_pos.py", "traced_leak_neg.py",
         "nds_tpu/engine/fx.py", 3),
        ("NDSJ302", "blind_capture_pos.py", "blind_capture_neg.py",
         "nds_tpu/engine/fx.py", 1),
        ("NDSJ303", "implicit_transfer_pos.py",
         "implicit_transfer_neg.py", "nds_tpu/engine/fx.py", 3),
        ("NDSJ303", "serve_blocking_pos.py", "serve_blocking_neg.py",
         "nds_tpu/serve/fx.py", 1),
        ("NDSJ304", "weak_literal_pos.py", "weak_literal_neg.py",
         "nds_tpu/engine/fx.py", 1),
    ]

    @pytest.mark.parametrize("rule,pos,neg,path,n", CASES,
                             ids=[f"{c[0]}-{c[1]}" for c in CASES])
    def test_positive_fires(self, rule, pos, neg, path, n):
        res = _scan(pos, path)
        hits = _hits(res, rule)
        assert len(hits) >= n, (
            f"{rule} missed its seeded hazard in {pos}: "
            f"{[str(v) for v in res.violations]}")
        # every seeded line is marked in the fixture with the rule id
        src = (FIXTURES / pos).read_text().splitlines()
        for v in hits:
            assert rule in src[v.line - 1], (
                f"{rule} fired on unmarked line {v.line}: "
                f"{src[v.line - 1]!r}")

    @pytest.mark.parametrize("rule,pos,neg,path,n", CASES,
                             ids=[f"{c[0]}-{c[2]}" for c in CASES])
    def test_negative_silent(self, rule, pos, neg, path, n):
        res = _scan(neg, path)
        assert _hits(res, rule) == [], (
            f"{rule} false-positived on {neg}: "
            f"{[str(v) for v in res.violations]}")

    def test_rules_path_scoped(self):
        # the same hazard text outside the audited trees is ignored
        src = (FIXTURES / "implicit_transfer_pos.py").read_text()
        res = jit_hazards.scan_sources({"nds_tpu/obs/fx.py": src})
        assert _hits(res, "NDSJ303") == []


class TestSuppressionGrammar:
    SRC = ('"""mod."""\n'
           "def run(compiled, bufs):\n"
           "    return compiled(bufs, 512){marker}\n")

    def _scan_src(self, marker: str):
        src = self.SRC.format(marker=marker)
        return jit_hazards.scan_sources({"nds_tpu/engine/fx.py": src})

    def test_unsuppressed_fires(self):
        res = self._scan_src("")
        assert len(_hits(res, "NDSJ304")) == 1

    def test_waive_form(self):
        res = self._scan_src(
            "  # ndsjit: waive[NDSJ304] -- zero-arg probe, one key")
        assert res.violations == [] and res.errors == []
        assert [v.rule for v in res.waived] == ["NDSJ304"]
        assert "probe" in res.waived[0].waiver_note

    def test_waive_without_note_is_error(self):
        res = self._scan_src("  # ndsjit: waive[NDSJ304]")
        assert any(e.rule == "NDSJ300" for e in res.errors)

    def test_disable_form_needs_no_note(self):
        res = self._scan_src("  # ndsjit: disable=NDSJ304")
        assert res.violations == [] and res.errors == []
        assert [v.rule for v in res.waived] == ["NDSJ304"]

    def test_stale_disable_is_error(self):
        src = ('"""mod."""\n'
               "def run(n):\n"
               "    return n + 1  # ndsjit: disable=NDSJ304\n")
        res = jit_hazards.scan_sources({"nds_tpu/engine/fx.py": src})
        assert any(e.rule == "NDSJ300"
                   and "matches no violation" in e.msg
                   for e in res.errors)

    def test_marker_inside_string_literal_ignored(self):
        # a marker spelled in a string (this very test file's idiom)
        # must not parse as a suppression of the embedding file
        src = ('"""mod."""\n'
               "TEXT = '# ndsjit: disable=NDSJ304'\n")
        res = jit_hazards.scan_sources({"nds_tpu/engine/fx.py": src})
        assert res.errors == [] and res.waived == []


class TestJitsanRuntime:
    """The seeded-hazard proof on a PRIVATE sanitizer: a deliberate
    post-warmup recompile and a hidden ``.item()`` must both land in
    the window verdict, and the declared read-back must not."""

    @pytest.fixture()
    def jitsan(self):
        jax = pytest.importorskip("jax")
        del jax
        from nds_tpu.analysis import jitsan as js
        assert js.install(), "interposition failed to install"
        yield js
        # the hooks are process-global: restore for test isolation
        js.uninstall()

    def test_seeded_recompile_and_hidden_item_caught(self, jitsan):
        import jax
        import jax.numpy as jnp

        from nds_tpu.cache import aot as cache_aot

        san = jitsan.Sanitizer(metric=False)
        with jitsan.swapped(san):
            san.arm("test.seeded")
            buf = jnp.arange(8, dtype=jnp.float32)
            # the deliberate post-warmup recompile, through the
            # engine's one funnel — exactly a fingerprint gap's shape
            compiled = cache_aot.lower_and_compile(
                jax.jit(lambda x: x * 2), buf, kind="test_recompile")
            with jitsan.dispatch("test"):
                out = compiled(buf)
            _ = out[0].item()  # the hidden sync
            _ = jax.device_get(out)  # sanctioned twin must NOT flag
            with jitsan.declared("scoped readback"):
                _ = out[1].item()  # declared scope: silent by design
            v = san.disarm()
        assert [c["kind"] for c in v["compiles"]] == ["test_recompile"]
        assert len(v["undeclared_transfers"]) == 1
        assert v["undeclared_transfers"][0]["what"] == ".item()"
        assert v["declared_transfers"] >= 1
        assert v["dispatches"] == 1

    def test_dispatch_guard_rejects_host_buffer(self, jitsan):
        import jax
        import numpy as np

        from nds_tpu.cache import aot as cache_aot

        host = np.ones((4,), dtype=np.float32)
        compiled = cache_aot.lower_and_compile(
            jax.jit(lambda x: x + 1), host, kind="test_guard")
        san = jitsan.Sanitizer(metric=False)
        with jitsan.swapped(san):
            san.arm("test.guard")
            with pytest.raises(Exception, match="[Tt]ransfer"):
                with jitsan.dispatch("test"):
                    compiled(host)  # implicit h2d inside the window
            san.disarm()

    def test_disarmed_is_transparent(self, jitsan):
        import jax.numpy as jnp
        san = jitsan.Sanitizer(metric=False)
        with jitsan.swapped(san):
            buf = jnp.ones((2,), jnp.float32)
            with jitsan.dispatch("noop"):
                _ = float(buf[0])  # disarmed: nothing records
            v = san.snapshot()
        assert v["windows"] == [] and san.undeclared == []

    def test_selftest(self, jitsan):
        assert jitsan.selftest()

    def test_install_is_live_on_this_jaxlib(self, jitsan):
        """The interposition really sits on the array type jax hands
        out (jaxlib moved it once: install() then "degraded" in silence
        and the transfer gate enforced nothing)."""
        import jax.numpy as jnp
        hooked = type(jnp.zeros(1)).item
        assert hooked.__closure__ is not None      # our wrapper
        assert jitsan.install() is True            # idempotent

    def test_install_raises_when_it_cannot_interpose(self, monkeypatch):
        from nds_tpu.analysis import jitsan as js
        js.uninstall()
        monkeypatch.setattr(js, "_hook_method", lambda *a: False)
        with pytest.raises(RuntimeError, match="cannot interpose"):
            js.install()
        with pytest.raises(RuntimeError, match="cannot interpose"):
            js.arm("gate", force=True)   # an armed gate never degrades
        assert js._originals == {}


def test_static_catalog_covers_documented_rules():
    ids = {r.id for r in jit_hazards.default_rules()}
    assert ids == {"NDSJ301", "NDSJ302", "NDSJ303", "NDSJ304"}
