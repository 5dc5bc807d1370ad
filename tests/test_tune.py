"""Ingest autotuner + tuning profiles (blit/tune.py; ISSUE 8 tentpole).

The convergence tests replace the stopwatch with a SIMULATED stage-cost
model, so they are deterministic on CPU and need no accelerator: the
model encodes a known optimum and the sweep must find it — twice, with
identical trial sequences.
"""

import json
import os

import pytest

jax = pytest.importorskip("jax")

from blit import tune as T  # noqa: E402


def cost_model(optimum, *, scale=1.0):
    """A convex (single-basin) synthetic GB/s surface peaking at
    ``optimum``: each knob contributes a penalty growing with its
    log/step distance from the optimum — the shape real sweeps show
    (too-small chunks pay dispatch overhead, too-deep rotations pay
    memory pressure)."""
    import math

    def measure(knobs):
        pen = 0.0
        pen += abs(math.log2(knobs["chunk_frames"])
                   - math.log2(optimum["chunk_frames"]))
        pen += 0.5 * abs(knobs["prefetch_depth"]
                         - optimum["prefetch_depth"])
        pen += 0.5 * abs(knobs["out_depth"] - optimum["out_depth"])
        return scale * 10.0 / (1.0 + pen)

    return measure


class TestOfflineConvergence:
    def test_converges_to_model_optimum(self):
        opt = {"chunk_frames": 32, "prefetch_depth": 4, "out_depth": 3}
        best, trials = T.tune(
            cost_model(opt),
            base={"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2},
            max_trials=40,
        )
        assert best == opt
        assert len(trials) <= 40

    def test_base_clamped_into_loadable_bounds(self):
        # A caller base above the sweep's own ladder bounds must be
        # clamped BEFORE scoring — otherwise an out-of-range base can
        # win, persist, and be silently rejected by load_profile on
        # every later run (tuning.source reads "default" while the
        # operator believes the rig is tuned).
        best, trials = T.tune(
            lambda k: 1.0,
            base={"chunk_frames": T.MAX_CHUNK_FRAMES * 4,
                  "prefetch_depth": 99, "out_depth": 0},
            max_trials=12,
        )
        assert 0 < best["chunk_frames"] <= T.MAX_CHUNK_FRAMES
        assert T.MIN_DEPTH <= best["prefetch_depth"] <= T.MAX_DEPTH
        assert T.MIN_DEPTH <= best["out_depth"] <= T.MAX_DEPTH
        for t in trials:  # no candidate ever left the loadable range
            assert t["chunk_frames"] <= T.MAX_CHUNK_FRAMES

    def test_deterministic_trial_sequence(self):
        opt = {"chunk_frames": 16, "prefetch_depth": 3, "out_depth": 2}
        runs = [T.tune(cost_model(opt), base={"chunk_frames": 4},
                       max_trials=30) for _ in range(2)]
        assert runs[0][0] == runs[1][0] == opt
        assert runs[0][1] == runs[1][1]  # identical evaluation log

    def test_respects_nint_granularity(self):
        # chunk_frames candidates stay multiples of nint (integration
        # windows must not straddle chunks — the RawReducer contract).
        opt = {"chunk_frames": 24, "prefetch_depth": 2, "out_depth": 2}
        best, trials = T.tune(cost_model(opt), nint=6,
                              base={"chunk_frames": 6}, max_trials=40)
        assert all(t["chunk_frames"] % 6 == 0 for t in trials)
        assert best["chunk_frames"] % 6 == 0

    def test_integration_no_dispatch_holds_is_not_rounded_up_to(self):
        # ONE rule says whether an integration fits a dispatch
        # (blit.pipeline.fold_frames) and tune takes its answer as the
        # multiple chunk_frames moves in.  rawspec's -f 1048576 -t 51 on
        # a 300 s scan (838 frames, half = 419): the budget holds 8
        # frames, the reducer carries the integration (ISSUE 26), and
        # neither the bound, the base, the ladder nor the online
        # recommendation may say 51 — the parent's 14.5 GB chunk.
        from blit.pipeline import dispatch_frames, fold_frames

        assert dispatch_frames(1 << 20) == 8
        fold = fold_frames(1 << 20, 51)
        assert fold == 1
        assert fold_frames(1024, 51) == 51          # fits: still folds
        assert fold_frames(1024, 3072) == 3072      # rawspec's 0002
        assert fold_frames(1 << 20, 8) == 8
        assert T._cf_bound(fold, 419) == 419
        base = T.normalize_base({"chunk_frames": 8}, nint=fold,
                                max_chunk_frames=419)
        assert base["chunk_frames"] == 8
        assert T.normalize_base({"chunk_frames": 8}, nint=6,
                                max_chunk_frames=27)["chunk_frames"] == 12
        opt = {"chunk_frames": 16, "prefetch_depth": 2, "out_depth": 2}
        best, trials = T.tune(cost_model(opt), nint=fold,
                              base={"chunk_frames": 8}, max_trials=30,
                              max_chunk_frames=419)
        assert best["chunk_frames"] == 16
        assert not any(t["chunk_frames"] % 51 == 0 for t in trials)
        rec = T.recommend_from_stages(
            {"dispatch": {"calls": 6, "seconds": 3.0},
             "device": {"calls": 6, "seconds": 6.0}}, {},
            {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2},
            nint=fold)
        assert rec.knobs["chunk_frames"] == 16

    def test_blit_tune_stays_inside_the_dispatch_budget_when_carried(
            self, monkeypatch):
        # `blit tune --nfft 1048576 --nint 51` on a recording of 838
        # frames: what _cmd_tune hands to normalize_base / tune.  Caught
        # at the first reduction it would run (the warm-up at the base).
        import argparse

        import blit.__main__ as cli
        import blit.io.guppi as guppi

        class Rdr:
            nblocks = 1676

            def header(self, i):
                return {"OBSNCHAN": 64}

            def block_ntime_kept(self, i):
                return 1 << 19

        class Stop(Exception):
            pass

        seen = {}

        def warm(self, raw, out):
            seen["chunk_frames"] = self.chunk_frames
            seen["carries"] = self._carries
            raise Stop

        from blit.pipeline import RawReducer

        monkeypatch.setattr(guppi, "open_raw", lambda p: Rdr())
        monkeypatch.setattr("os.path.getsize", lambda p: 1)
        monkeypatch.setattr(RawReducer, "reduce_to_file", warm)
        args = argparse.Namespace(
            raw="scan.raw", nfft=1 << 20, nint=51, fqav=1,
            dtype="float32", nbits=8, chunk_frames=8, trials=4, reps=1)
        with pytest.raises(Stop):
            cli._cmd_tune(args)
        assert seen == {"chunk_frames": 8, "carries": True}
        args.chunk_frames = 64  # asked beyond the budget: clamped to it
        with pytest.raises(Stop):
            cli._cmd_tune(args)
        assert seen == {"chunk_frames": 8, "carries": True}

    def test_budget_bounds_measurements(self):
        opt = {"chunk_frames": 1024, "prefetch_depth": 8, "out_depth": 8}
        _, trials = T.tune(cost_model(opt), base={"chunk_frames": 8},
                           max_trials=5)
        assert len(trials) == 5

    def test_marginally_worse_smaller_knob_wins_tie(self):
        # A smaller candidate WITHIN rel_tol of best (even slightly
        # below) is a tie and the smaller knob wins — measurement noise
        # must not ratchet the sweep toward big knobs.
        def measure(k):
            return 1.0 if k["prefetch_depth"] >= 3 else 0.995

        best, _ = T.tune(measure,
                         base={"chunk_frames": 8, "prefetch_depth": 3,
                               "out_depth": 2},
                         max_trials=20, rel_tol=0.01)
        assert best["prefetch_depth"] == T.MIN_DEPTH

    def test_flat_surface_keeps_smaller_knobs(self):
        # Ties (within rel_tol) must prefer the cheaper setting, not
        # drift toward deep rotations that buy nothing.
        best, _ = T.tune(lambda k: 1.0,
                         base={"chunk_frames": 8, "prefetch_depth": 3,
                               "out_depth": 3}, max_trials=30)
        assert best["prefetch_depth"] == T.MIN_DEPTH
        assert best["out_depth"] == T.MIN_DEPTH


class TestProfileStore:
    def _mkprofile(self, **fp_kw):
        key, ident = T.rig_fingerprint(**fp_kw)
        return T.TuningProfile(key=key, rig=ident, chunk_frames=16,
                               prefetch_depth=3, out_depth=4,
                               score_gbps=1.5, trials=9)

    def test_round_trip(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        prof = self._mkprofile(nfft=1024, nint=1)
        path = T.save_profile(prof)
        assert os.path.dirname(path) == str(tmp_path)
        got = T.load_profile(prof.key)
        assert got is not None
        assert got.knobs() == prof.knobs()
        assert got.score_gbps == prof.score_gbps
        assert got.rig == prof.rig
        # and through the public lookup:
        hit = T.lookup(nfft=1024, nint=1)
        assert hit is not None and hit.knobs() == prof.knobs()

    def test_missing_and_corrupt_ignored(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        key, _ = T.rig_fingerprint(nfft=512, nint=1)
        assert T.load_profile(key) is None
        with open(T._profile_path(key), "w") as f:
            f.write("{not json")
        assert T.load_profile(key) is None

    def test_corrupt_or_unbounded_knobs_ignored(self, tmp_path,
                                                monkeypatch):
        """The integrity hash covers only the rig identity — knob values
        must be validated separately, and a bad profile must be IGNORED
        (never crash RawReducer construction: reduce/scan/serve/stream
        would all be dead on that rig until the file is deleted)."""
        import json as _json

        from blit.pipeline import RawReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        prof = self._mkprofile(nfft=1024, nint=1)
        path = T.save_profile(prof)
        for bad in (None, "junk", 0, -1, T.MAX_CHUNK_FRAMES * 8):
            doc = _json.load(open(path))
            doc["chunk_frames"] = bad
            with open(path, "w") as f:
                _json.dump(doc, f)
            assert T.load_profile(prof.key) is None, bad
        doc = _json.load(open(path))
        doc["chunk_frames"] = 8
        doc["out_depth"] = T.MAX_DEPTH + 100  # tampered-but-numeric
        with open(path, "w") as f:
            _json.dump(doc, f)
        assert T.load_profile(prof.key) is None
        # And the reducer construction path survives a bad profile for
        # ITS key too (falls back to defaults, no exception).
        key, ident = T.rig_fingerprint(
            **RawReducer(nfft=64, nint=2)._tune_fingerprint_kw())
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=6, prefetch_depth=3,
            out_depth=4))
        p2 = T._profile_path(key)
        doc = _json.load(open(p2))
        doc["chunk_frames"] = None
        with open(p2, "w") as f:
            _json.dump(doc, f)
        red = RawReducer(nfft=64, nint=2)
        assert red.tuning_provenance()["sources"]["chunk_frames"] == \
            "default"

    def test_stale_profile_for_other_rig_ignored(self, tmp_path,
                                                 monkeypatch):
        # Regression pin (ISSUE 8 satellite): a profile copied from a
        # different rig fingerprint must be IGNORED, not trusted.  Write
        # a valid profile, then store it under the key of a DIFFERENT
        # workload shape — load must reject the identity mismatch.
        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        prof = self._mkprofile(nfft=1024, nint=1)
        other_key, _ = T.rig_fingerprint(nfft=2048, nint=1)
        prof.key = other_key  # content no longer hashes to its key
        T.save_profile(prof)
        assert T.load_profile(other_key) is None
        assert T.lookup(nfft=2048, nint=1) is None

    def test_workload_shape_selects_profile(self, tmp_path, monkeypatch):
        # Different nfft → different key → no crosstalk.
        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        T.save_profile(self._mkprofile(nfft=1024, nint=1))
        assert T.lookup(nfft=1024, nint=1) is not None
        assert T.lookup(nfft=4096, nint=1) is None
        assert T.lookup(nfft=1024, nint=16) is None

    def test_site_config_tune_dir_applies_without_explicit_config(
            self, tmp_path, monkeypatch):
        """SiteConfig.tune_dir must govern the default (config=None)
        path every production caller uses — not just an explicitly
        passed config object (the hostmem staging_pool_bytes rule).
        Env still wins."""
        from blit import config as C

        monkeypatch.delenv("BLIT_TUNE_DIR", raising=False)
        monkeypatch.setattr(C.DEFAULT, "tune_dir", str(tmp_path / "site"))
        assert T.profile_dir() == str(tmp_path / "site")
        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path / "env"))
        assert T.profile_dir() == str(tmp_path / "env")

    def test_disabled_by_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        T.save_profile(self._mkprofile(nfft=1024, nint=1))
        monkeypatch.setenv("BLIT_TUNE", "0")
        assert T.lookup(nfft=1024, nint=1) is None


class TestReducerAutoload:
    def test_reducer_loads_profile_automatically(self, tmp_path,
                                                 monkeypatch):
        from blit.pipeline import RawReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        red0 = RawReducer(nfft=64, nint=2)  # no profile yet: defaults
        assert red0.tuning_provenance()["sources"]["chunk_frames"] == \
            "default"
        key, ident = T.rig_fingerprint(
            **RawReducer(nfft=64, nint=2)._tune_fingerprint_kw())
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=6, prefetch_depth=3,
            out_depth=4))
        red = RawReducer(nfft=64, nint=2)
        assert (red.chunk_frames, red.prefetch_depth, red.out_depth) == \
            (6, 3, 4)
        prov = red.tuning_provenance()
        assert prov["sources"] == {k: "profile" for k in T.KNOBS}
        assert prov["profile"]["key"] == key
        # Explicit knobs always win over the profile.
        red2 = RawReducer(nfft=64, nint=2, chunk_frames=8,
                          prefetch_depth=2)
        assert red2.chunk_frames == 8 and red2.prefetch_depth == 2
        assert red2.out_depth == 4  # unset knob still resolves from it
        # And the kill switch restores the defaults.
        monkeypatch.setenv("BLIT_TUNE", "0")
        red3 = RawReducer(nfft=64, nint=2)
        assert red3.chunk_frames != 6 and red3.prefetch_depth == 2

    def test_profile_chunk_frames_rounded_to_nint(self, tmp_path,
                                                  monkeypatch):
        from blit.pipeline import RawReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        key, ident = T.rig_fingerprint(
            **RawReducer(nfft=64, nint=4)._tune_fingerprint_kw())
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=6, prefetch_depth=2,
            out_depth=2))
        red = RawReducer(nfft=64, nint=4)
        assert red.chunk_frames % 4 == 0  # the nint rounding still runs

    def test_profile_beyond_the_budget_is_resized_when_carried(
            self, tmp_path, monkeypatch):
        # A profile the parent's `blit tune` wrote for -f 1048576 -t 51
        # says 51 or 102 frames (rounded up to the integration): 14.5 GB
        # a chunk.  The budget holds 8; the reducer re-sizes, and an
        # explicit chunk_frames is still honoured as given.
        from blit.pipeline import RawReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        key, ident = T.rig_fingerprint(
            **RawReducer(nfft=1 << 20, nint=51)._tune_fingerprint_kw())
        for saved, want in [(51, 8), (102, 8), (4, 4)]:
            T.save_profile(T.TuningProfile(
                key=key, rig=ident, chunk_frames=saved, prefetch_depth=2,
                out_depth=2))
            red = RawReducer(nfft=1 << 20, nint=51)
            assert red.tuning_provenance()["sources"][
                "chunk_frames"] == "profile"
            assert red.chunk_frames == want and red._carries
        assert RawReducer(nfft=1 << 20, nint=51,
                          chunk_frames=51).chunk_frames == 51

    def test_profile_nchan_mismatch_warns_once(self, tmp_path, monkeypatch,
                                               caplog):
        """nchan is deliberately NOT in the fingerprint key (lookup
        happens before any recording is open) — so a profile measured on
        a different-width recording must at least announce itself: one
        warning per stream plus a provenance block naming both widths."""
        import logging

        from blit.pipeline import RawReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        key, ident = T.rig_fingerprint(
            **RawReducer(nfft=64, nint=2)._tune_fingerprint_kw())
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=6, prefetch_depth=3,
            out_depth=4, tuned_nchan=8))
        red = RawReducer(nfft=64, nint=2)
        with caplog.at_level(logging.WARNING, logger="blit.pipeline"):
            red._note_stream_nchan(2)
            red._note_stream_nchan(2)  # same stream width: no repeat
        warns = [r for r in caplog.records
                 if "tuning profile" in r.getMessage()]
        assert len(warns) == 1
        assert red.tuning_provenance()["profile_nchan_mismatch"] == {
            "tuned": 8, "stream": 2}
        # Matching width, or a legacy profile (tuned_nchan=0), is silent.
        red2 = RawReducer(nfft=64, nint=2)
        red2._note_stream_nchan(8)
        assert "profile_nchan_mismatch" not in red2.tuning_provenance()

    def test_search_reducer_inherits_profile(self, tmp_path, monkeypatch):
        from blit.pipeline import RawReducer
        from blit.search import DedopplerReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        key, ident = T.rig_fingerprint(
            **RawReducer(nfft=128, nint=1)._tune_fingerprint_kw())
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=8, prefetch_depth=4,
            out_depth=5))
        red = DedopplerReducer(nfft=128, nint=1, window_spectra=8)
        assert (red.prefetch_depth, red.out_depth) == (4, 5)


class TestOnlineTuner:
    def _stages(self, *, disp, dev, ingest=0.0, wall=1.0, calls=8):
        return {
            "dispatch": {"seconds": disp * calls, "calls": calls},
            "device": {"seconds": dev * calls, "calls": calls},
            "ingest": {"seconds": ingest, "calls": calls},
            "stream": {"seconds": wall, "calls": 1},
        }

    def test_dispatch_bound_doubles_chunk(self):
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        rec = T.recommend_from_stages(
            self._stages(disp=0.5, dev=1.0), {}, cur)
        assert rec.knobs["chunk_frames"] == 16
        assert any("dispatch-bound" in r for r in rec.reasons)

    def test_readback_lag_deepens_out(self):
        # PERSISTENT lag (median, not a single burst) is the deepen
        # signal — p99 over ~8 warmup samples is just the max, and chunk
        # 1's compile-sized sample would trip it on every cold run.
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        hists = {
            "out.readback_lag_s": {"n": 8, "p50": 0.2, "p99": 0.5},
            "out.chunk_latency_s": {"n": 8, "p50": 0.05, "p99": 0.1},
        }
        rec = T.recommend_from_stages(
            self._stages(disp=0.01, dev=1.0), hists, cur)
        assert rec.knobs["out_depth"] == 3
        # One outlier in an otherwise healthy plane does NOT deepen.
        hists["out.readback_lag_s"] = {"n": 8, "p50": 0.05, "p99": 5.0}
        rec = T.recommend_from_stages(
            self._stages(disp=0.01, dev=1.0), hists, cur)
        assert rec.knobs["out_depth"] == 2

    def test_producer_bound_deepens_prefetch(self):
        # Per-chunk file read dominates per-chunk hidden work — and the
        # rule must hold MID-STREAM, where the 'stream' wall stage has
        # not yet closed (its seconds read 0 until stream end).
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        stages = self._stages(disp=0.01, dev=0.1, ingest=8 * 0.5, wall=0.0)
        rec = T.recommend_from_stages(stages, {}, cur)
        assert rec.knobs["prefetch_depth"] == 3
        assert any("producer-bound" in r for r in rec.reasons)

    def test_balanced_plane_changes_nothing(self):
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        rec = T.recommend_from_stages(
            self._stages(disp=0.01, dev=1.0), {}, cur)
        assert rec.knobs == cur and rec.reasons == []

    def test_converges_during_first_windows(self):
        # The tuner reads the timeline ONCE, at the warmup boundary, and
        # publishes tune.rec_* gauges — then goes dormant.
        from blit.observability import Timeline

        tl = Timeline()
        with tl.stage("stream"):
            pass
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        tuner = T.OnlineTuner(tl, cur, warmup_chunks=4)
        for i in range(4):
            tl.stages["dispatch"].calls += 1
            tl.stages["dispatch"].seconds += 0.5
            tl.stages["device"].calls += 1
            tl.stages["device"].seconds += 1.0
            tuner.observe_chunk()
            assert tuner.converged == (i == 3)
        assert tuner.recommendation.knobs["chunk_frames"] == 16
        assert tl.gauges["tune.rec_chunk_frames"].last == 16.0

    def test_first_chunk_compile_excluded(self):
        # Chunk 1's dispatch stage includes the XLA compile; a cold run
        # must not look dispatch-bound because of it (regression: the
        # online recommendation doubled chunk_frames on every cold run,
        # ratcheting the persisted profile x2 per run under
        # BLIT_TUNE_ONLINE=1).
        from blit.observability import Timeline

        tl = Timeline()
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        tuner = T.OnlineTuner(tl, cur, warmup_chunks=4)
        for i in range(4):
            tl.stages["dispatch"].calls += 1
            tl.stages["dispatch"].seconds += 5.0 if i == 0 else 0.01
            tl.stages["device"].calls += 1
            tl.stages["device"].seconds += 1.0
            tuner.observe_chunk()
            # REAL pipeline ordering: the readback thread records chunk
            # i's lag AFTER observe_chunk(i) — so chunk 1's
            # compile-sized sample lands after the tuner's snapshot and
            # survives the hist delta.  The median-based heuristic must
            # shrug it off anyway.
            tl.observe("out.readback_lag_s", 5.0 if i == 0 else 0.001)
            tl.observe("out.chunk_latency_s", 0.01)
        assert tuner.converged
        assert tuner.recommendation.knobs == cur  # compile not counted

    def test_persistence_is_opt_in(self, tmp_path, monkeypatch):
        from blit.observability import Timeline

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        monkeypatch.delenv("BLIT_TUNE_ONLINE", raising=False)
        tl = Timeline()
        cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
        tuner = T.OnlineTuner(tl, cur, warmup_chunks=2)
        for _ in range(2):
            tl.stages["dispatch"].calls += 1
            tl.stages["dispatch"].seconds += 0.5
            tl.stages["device"].calls += 1
            tl.stages["device"].seconds += 1.0
            tuner.observe_chunk()
        assert tuner.converged
        assert tuner.maybe_persist(nfft=64, nint=1) is None
        assert os.listdir(tmp_path) == []
        monkeypatch.setenv("BLIT_TUNE_ONLINE", "1")
        path = tuner.maybe_persist(nfft=64, nint=1)
        assert path is not None and os.path.exists(path)
        prof = T.lookup(nfft=64, nint=1)
        assert prof is not None and prof.source == "online"
        assert prof.chunk_frames == 16

    def test_online_never_clobbers_measured_offline(self, tmp_path,
                                                    monkeypatch):
        # A `blit tune` sweep MEASURED its knobs; the online heuristic is
        # one warmup window, possibly under a transient load spike.  With
        # BLIT_TUNE_ONLINE=1 the recommendation must not replace the
        # measured profile at the same key — but may replace a prior
        # ONLINE profile (heuristic vs heuristic: newest wins).
        from blit.observability import Timeline

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        monkeypatch.setenv("BLIT_TUNE_ONLINE", "1")
        key, ident = T.rig_fingerprint(nfft=64, nint=1)
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=4, prefetch_depth=2,
            out_depth=2, score_gbps=1.5, source="offline"))

        def converged_tuner():
            tl = Timeline()
            cur = {"chunk_frames": 8, "prefetch_depth": 2, "out_depth": 2}
            tuner = T.OnlineTuner(tl, cur, warmup_chunks=2)
            for _ in range(2):
                tl.stages["dispatch"].calls += 1
                tl.stages["dispatch"].seconds += 0.5
                tl.stages["device"].calls += 1
                tl.stages["device"].seconds += 1.0
                tuner.observe_chunk()
            assert tuner.converged
            return tuner

        assert converged_tuner().maybe_persist(nfft=64, nint=1) is None
        prof = T.load_profile(key)
        assert prof.source == "offline" and prof.chunk_frames == 4
        # An online profile at the key IS replaceable.
        T.save_profile(T.TuningProfile(
            key=key, rig=ident, chunk_frames=4, prefetch_depth=2,
            out_depth=2, source="online"))
        assert converged_tuner().maybe_persist(nfft=64, nint=1) is not None
        assert T.load_profile(key).chunk_frames == 16

    def test_online_profile_feeds_next_run(self, tmp_path, monkeypatch):
        # End to end: a reduction run under BLIT_TUNE_ONLINE=1 persists
        # its converged recommendation; the NEXT reducer construction
        # picks it up automatically.
        from blit.pipeline import RawReducer
        from blit.testing import synth_raw

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path))
        monkeypatch.setenv("BLIT_TUNE_ONLINE", "1")
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=4096)
        red = RawReducer(nfft=64, nint=1, chunk_frames=4)
        red.reduce_to_file(p, str(tmp_path / "x.fil"))
        # Whatever the tuner decided, a persisted profile (if its
        # recommendation moved a knob) must round-trip into a fresh
        # reducer; a no-move run persists nothing and defaults hold.
        prof = T.lookup(**red._tune_fingerprint_kw())
        red2 = RawReducer(nfft=64, nint=1)
        if prof is not None:
            assert red2.chunk_frames == prof.chunk_frames
        else:
            assert red2.tuning_provenance()["sources"]["chunk_frames"] \
                == "default"


class TestTuneCLI:
    def test_tune_then_scan_loads_profile(self, tmp_path, monkeypatch,
                                          capsys):
        """The acceptance pin: `blit tune` writes a profile; a
        subsequent `blit scan` on the same rig (same workload shape,
        no --window-frames) loads it automatically and reports the
        provenance."""
        from blit.__main__ import main
        from blit.testing import build_observation_tree

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path / "profiles"))
        rc = main(["tune", "--nfft", "64", "--nint", "2", "--nchan", "2",
                   "--chunk-frames", "4", "--chunks", "2", "--blocks", "2",
                   "--trials", "3"])
        out = capsys.readouterr().out
        assert rc == 0
        rep = json.loads(out)
        assert os.path.exists(rep["profile"])
        assert rep["trials"] and rep["winner"]

        root = str(tmp_path / "datax")
        build_observation_tree(root, kind="raw", players=((0, 0), (0, 1)),
                               nchans=2, nfiles=2, raw_ntime=512)
        rc = main(["scan", root, "AGBT22B_999_01", "0011",
                   "-o", str(tmp_path), "--nfft", "64", "--nint", "2"])
        out = capsys.readouterr().out
        assert rc == 0
        stats = json.loads(out.strip().splitlines()[-1])
        assert stats["tuning"]["source"] == "profile"
        assert stats["tuning"]["key"] == rep["key"]
        # The executed window is the profile's chunk_frames (nint-rounded).
        want = max((rep["winner"]["chunk_frames"] // 2) * 2, 2)
        assert stats["window_frames"] == want

    def test_reduce_uses_profile_after_tune(self, tmp_path, monkeypatch,
                                            capsys):
        from blit.__main__ import main
        from blit.pipeline import RawReducer

        monkeypatch.setenv("BLIT_TUNE_DIR", str(tmp_path / "profiles"))
        rc = main(["tune", "--nfft", "64", "--nint", "1", "--nchan", "2",
                   "--chunk-frames", "4", "--chunks", "2", "--blocks", "2",
                   "--trials", "3"])
        rep = json.loads(capsys.readouterr().out)
        assert rc == 0
        red = RawReducer(nfft=64, nint=1)
        assert red.chunk_frames == rep["winner"]["chunk_frames"]
        assert red.prefetch_depth == rep["winner"]["prefetch_depth"]
        assert red.out_depth == rep["winner"]["out_depth"]
