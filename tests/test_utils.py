"""Utils coverage: DateRange + date-partitioned discovery (reference
util/DateRange + IOUtils) and the training event system (reference event/).
"""

import datetime

import numpy as np
import pytest

from photon_ml_tpu.utils import events as ev
from photon_ml_tpu.utils.ranges import (DateRange, DoubleRange,
                                        input_paths_within_date_range)


class TestDateRange:
    def test_parse_reference_form(self):
        r = DateRange.parse("20160101-20160131")
        assert r.start == datetime.date(2016, 1, 1)
        assert r.end == datetime.date(2016, 1, 31)
        assert len(list(r.days())) == 31

    def test_parse_iso_form(self):
        r = DateRange.parse("2016-01-01:2016-01-03")
        assert [d.day for d in r.days()] == [1, 2, 3]

    def test_invalid(self):
        with pytest.raises(ValueError):
            DateRange.parse("20160131-20160101")
        with pytest.raises(ValueError):
            DateRange.parse("garbage")

    def test_contains(self):
        r = DateRange.parse("20160110-20160120")
        assert r.contains(datetime.date(2016, 1, 15))
        assert not r.contains(datetime.date(2016, 1, 21))

    def test_input_discovery(self, tmp_path):
        for day in (1, 2, 4):
            (tmp_path / "2016" / "01" / f"{day:02d}").mkdir(parents=True)
        r = DateRange.parse("20160101-20160105")
        found = input_paths_within_date_range(str(tmp_path), r)
        assert [p[-10:] for p in found] == ["2016/01/01", "2016/01/02",
                                           "2016/01/04"]
        with pytest.raises(FileNotFoundError):
            input_paths_within_date_range(str(tmp_path), r,
                                          errors_on_missing=True)


class TestEvents:
    def test_emit_and_listener_lifecycle(self):
        emitter = ev.EventEmitter()
        seen = []
        emitter.register(seen.append)
        emitter.emit(ev.TrainingStart(task="LOGISTIC_REGRESSION",
                                      update_sequence=("fixed",),
                                      iterations=2))
        emitter.emit(ev.CoordinateUpdate(iteration=0, coordinate="fixed",
                                         train_seconds=0.1))
        assert len(seen) == 2
        emitter.unregister(seen.append)
        emitter.emit(ev.TrainingFinish(task="LOGISTIC_REGRESSION",
                                       total_updates=2))
        assert len(seen) == 2

    def test_raising_listener_is_detached(self):
        emitter = ev.EventEmitter()
        calls = []

        def bad(event):
            calls.append(event)
            raise RuntimeError("boom")

        emitter.register(bad)
        emitter.emit(ev.TrainingFinish(task="t", total_updates=1))
        emitter.emit(ev.TrainingFinish(task="t", total_updates=2))
        assert len(calls) == 1  # detached after the first failure

    def test_descent_emits_lifecycle(self, rng):
        from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                               FixedEffectDataConfiguration)
        from photon_ml_tpu.api.estimator import GameEstimator
        from photon_ml_tpu.data import synthetic
        from photon_ml_tpu.data.game_data import from_synthetic
        from photon_ml_tpu.optim.problem import GLMOptimizationConfiguration
        from photon_ml_tpu.parallel.mesh import make_mesh
        from photon_ml_tpu.types import TaskType

        seen = []
        ev.default_emitter.register(seen.append)
        try:
            ds = from_synthetic(synthetic.game_data(rng, n=256, d_global=6,
                                                    re_specs={}))
            cc = {"fixed": CoordinateConfiguration(
                data=FixedEffectDataConfiguration("global"),
                optimization=GLMOptimizationConfiguration())}
            GameEstimator(TaskType.LOGISTIC_REGRESSION, cc, ["fixed"],
                          make_mesh(), descent_iterations=2).fit(ds)
        finally:
            ev.default_emitter.unregister(seen.append)
        kinds = [type(e).__name__ for e in seen]
        assert kinds == ["TrainingStart", "CoordinateUpdate",
                         "CoordinateUpdate", "TrainingFinish"]
        assert seen[1].coordinate == "fixed"


class TestNativeLibsvm:
    """The C++ parser must agree exactly with the Python fallback."""

    def _fixture(self, tmp_path, rng, n=200, d=30):
        import os
        X = (rng.normal(size=(n, d)) *
             (rng.random((n, d)) < 0.3)).astype(np.float32)
        y = rng.choice([-1.0, 1.0], size=n)
        path = str(tmp_path / "data.txt")
        from photon_ml_tpu.data.libsvm import write_libsvm
        write_libsvm(path, X, y)
        with open(path, "a") as f:
            f.write("\n# trailing comment line\n")
        return path, X, y

    def test_native_matches_python(self, tmp_path, rng):
        from photon_ml_tpu.data import libsvm as lsv

        path, X, y = self._fixture(tmp_path, rng)
        if lsv._load_native() is None:
            pytest.skip("no native toolchain")
        native = lsv.read_libsvm(path, dense=True)

        # Force the Python fallback and compare.
        saved = lsv._native_lib, lsv._native_failed
        lsv._native_lib, lsv._native_failed = None, True
        try:
            fallback = lsv.read_libsvm(path, dense=True)
        finally:
            lsv._native_lib, lsv._native_failed = saved

        np.testing.assert_array_equal(native.labels, fallback.labels)
        np.testing.assert_allclose(native.dense, fallback.dense,
                                   rtol=1e-6, atol=0)
        assert native.num_features == fallback.num_features
        # And against the ground truth that wrote the file.
        np.testing.assert_allclose(
            native.dense, X[:, :native.num_features], rtol=1e-4, atol=1e-6)

    def test_native_error_reporting(self, tmp_path):
        from photon_ml_tpu.data import libsvm as lsv

        path = str(tmp_path / "bad.txt")
        with open(path, "w") as f:
            f.write("1 3:0.5\n1 nonsense\n")
        if lsv._load_native() is None:
            pytest.skip("no native toolchain")
        with pytest.raises(ValueError, match="line 2"):
            lsv.read_libsvm(path)

    def test_native_strictness_parity(self, tmp_path):
        """Malformed inputs must fail identically in both parsers: dangling
        'idx:', whitespace after ':', and mid-line '#' are all errors."""
        from photon_ml_tpu.data import libsvm as lsv

        if lsv._load_native() is None:
            pytest.skip("no native toolchain")
        cases = ["1 3:\n0 5:2\n", "1 3: 0.5\n", "1 2:0.5 # note\n"]
        for i, content in enumerate(cases):
            path = str(tmp_path / f"m{i}.txt")
            with open(path, "w") as f:
                f.write(content)
            with pytest.raises(ValueError):
                lsv.read_libsvm(path)  # native
            saved = lsv._native_lib, lsv._native_failed
            lsv._native_lib, lsv._native_failed = None, True
            try:
                with pytest.raises(ValueError):
                    lsv.read_libsvm(path)  # fallback
            finally:
                lsv._native_lib, lsv._native_failed = saved

    def test_missing_file_raises_filenotfound(self, tmp_path):
        from photon_ml_tpu.data import libsvm as lsv

        with pytest.raises(FileNotFoundError):
            lsv.read_libsvm(str(tmp_path / "nope.txt"))

    def test_index_overflow_and_hex_rejected_both_paths(self, tmp_path):
        """int32-overflowing indices and hex float values must error in
        BOTH parsers (native previously wrapped / accepted them)."""
        from photon_ml_tpu.data import libsvm as lsv

        if lsv._load_native() is None:
            pytest.skip("no native toolchain")
        for content in ("1 4294967297:1.0\n", "1 2:0x1A\n"):
            path = str(tmp_path / "x.txt")
            with open(path, "w") as f:
                f.write(content)
            with pytest.raises(ValueError):
                lsv.read_libsvm(path, zero_based=True)  # native
            saved = lsv._native_lib, lsv._native_failed
            lsv._native_lib, lsv._native_failed = None, True
            try:
                with pytest.raises(ValueError):
                    lsv.read_libsvm(path, zero_based=True)  # fallback
            finally:
                lsv._native_lib, lsv._native_failed = saved

    def test_plus_one_labels(self, tmp_path):
        """LIBSVM's '+1' label form parses in both paths."""
        from photon_ml_tpu.data import libsvm as lsv

        path = str(tmp_path / "plus.txt")
        with open(path, "w") as f:
            f.write("+1 1:0.5\n-1 2:1.5\n")
        d = lsv.read_libsvm(path, dense=True)
        np.testing.assert_array_equal(d.labels, [1.0, 0.0])  # ±1 -> {0,1}

    def test_numeric_edge_parity(self, tmp_path):
        """'+-1' labels error in both paths; out-of-range magnitudes keep
        strtod/Python semantics (overflow -> inf, underflow -> 0) in both."""
        from photon_ml_tpu.data import libsvm as lsv

        if lsv._load_native() is None:
            pytest.skip("no native toolchain")

        def both(content, check):
            path = str(tmp_path / "n.txt")
            with open(path, "w") as f:
                f.write(content)
            check(lambda: lsv.read_libsvm(path, dense=True,
                                          binary_labels_to_01=False))
            saved = lsv._native_lib, lsv._native_failed
            lsv._native_lib, lsv._native_failed = None, True
            try:
                check(lambda: lsv.read_libsvm(path, dense=True,
                                              binary_labels_to_01=False))
            finally:
                lsv._native_lib, lsv._native_failed = saved

        def expect_error(f):
            with pytest.raises(ValueError):
                f()

        both("+-1 1:0.5\n", expect_error)

        def expect_inf_and_zero(f):
            d = f()
            assert np.isinf(d.dense[0, 0])
            assert d.dense[1, 0] == 0.0

        both("1 1:9e999\n0 1:1e-999\n", expect_inf_and_zero)
