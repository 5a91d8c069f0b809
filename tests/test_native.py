"""Native C++ encoder vs the pure-Python path: exact parity required."""

import numpy as np
import pytest

from mlops_tpu.data import Preprocessor
from mlops_tpu.data.ingest import write_csv_columns
from mlops_tpu import native


@pytest.fixture(scope="module")
def csv_file(tmp_path_factory):
    from mlops_tpu.data import generate_synthetic

    columns, labels = generate_synthetic(500, seed=11)
    path = tmp_path_factory.mktemp("native") / "data.csv"
    write_csv_columns(path, columns, labels)
    return path, columns, labels


def test_native_builds():
    assert native.native_available(), (
        "g++ is in the image; the native encoder must build"
    )


def test_encoder_status_names_the_encoder_and_the_reason(monkeypatch):
    assert native.encoder_status() == {"encoder": "c++"}
    monkeypatch.setattr(native, "_lib_cache", None)
    monkeypatch.setenv("MLOPS_TPU_NO_NATIVE", "1")
    status = native.encoder_status()
    assert status["encoder"] == "python"
    assert "MLOPS_TPU_NO_NATIVE" in status["reason"]


def test_native_matches_python_exactly(csv_file):
    path, columns, labels = csv_file
    prep = Preprocessor.fit(columns)
    got = native.encode_csv_native(path, prep, require_target=True)
    want = prep.encode(columns, labels)
    np.testing.assert_array_equal(got.cat_ids, want.cat_ids)
    np.testing.assert_allclose(got.numeric, want.numeric, atol=1e-5)
    np.testing.assert_array_equal(got.labels, np.asarray(want.labels, np.int8))


def test_native_handles_oov_missing_and_quotes(tmp_path):
    from mlops_tpu.schema import SCHEMA

    header = (
        ",".join(f.name for f in SCHEMA.categorical)
        + ","
        + ",".join(f.name for f in SCHEMA.numeric)
    )
    cat_row1 = ['"male"'] + ["NEVER_SEEN"] * (SCHEMA.num_categorical - 1)
    num_row1 = ["", "null"] + ["1.5"] * (SCHEMA.num_numeric - 2)
    path = tmp_path / "edge.csv"
    path.write_text(
        header + "\n" + ",".join(cat_row1 + num_row1) + "\n"
    )

    columns = {f.name: ["male"] for f in SCHEMA.categorical}
    for f in SCHEMA.numeric:
        columns[f.name] = [1.0]
    prep = Preprocessor.fit(columns)

    got = native.encode_csv_native(path, prep)
    assert got.labels is None
    assert got.cat_ids.shape == (1, SCHEMA.num_categorical)
    # Quoted "male" decodes to id 0; unseen values hit each feature's OOV id.
    assert got.cat_ids[0, 0] == 0
    for j, feat in enumerate(SCHEMA.categorical[1:], start=1):
        assert got.cat_ids[0, j] == feat.oov_id
    # Missing numerics -> median (=1.0) -> standardized 0 (std floor 1.0).
    np.testing.assert_allclose(got.numeric[0, :2], 0.0, atol=1e-6)


def test_native_missing_column_errors(tmp_path):
    path = tmp_path / "bad.csv"
    path.write_text("only_one_column\nx\n")
    columns = {"credit_limit": [1.0]}
    from mlops_tpu.schema import SCHEMA

    full = {f.name: ["male"] for f in SCHEMA.categorical}
    for f in SCHEMA.numeric:
        full[f.name] = [1.0]
    prep = Preprocessor.fit(full)
    with pytest.raises(ValueError, match="missing"):
        native.encode_csv_native(path, prep)


def test_fallback_path_matches(csv_file, monkeypatch):
    path, columns, labels = csv_file
    prep = Preprocessor.fit(columns)
    monkeypatch.setattr(native, "_lib_cache", False)
    got = native.encode_csv(path, prep, require_target=True)
    want = prep.encode(columns, labels)
    np.testing.assert_array_equal(got.cat_ids, want.cat_ids)
    np.testing.assert_allclose(got.numeric, want.numeric, atol=1e-5)


def _tiny_prep():
    from mlops_tpu.schema import SCHEMA

    columns = {f.name: ["male"] for f in SCHEMA.categorical}
    for f in SCHEMA.numeric:
        columns[f.name] = [1.0]
    return Preprocessor.fit(columns)


def _edge_csv(tmp_path, rows, header=None, name="edge.csv"):
    from mlops_tpu.schema import SCHEMA

    if header is None:
        header = ",".join(f.name for f in SCHEMA.categorical) + "," + ",".join(
            f.name for f in SCHEMA.numeric
        )
    path = tmp_path / name
    path.write_text(header + "\n" + "\n".join(rows) + "\n")
    return path


def _both_paths(path, prep, require_target=False):
    from mlops_tpu.data.ingest import load_csv_columns

    got = native.encode_csv_native(path, prep, require_target=require_target)
    columns, labels = load_csv_columns(path, require_target=require_target)
    want = prep.encode(columns, labels)
    np.testing.assert_array_equal(got.cat_ids, want.cat_ids)
    np.testing.assert_allclose(got.numeric, want.numeric, atol=1e-5)
    return got, want


def test_parity_stray_quote_and_garbage_numerics(tmp_path):
    """csv.reader semantics: mid-field quotes stay literal; float() ones:
    '1.5abc' and hex reject -> median. Native must match Python exactly."""
    from mlops_tpu.schema import SCHEMA

    cats = ['5\'6" tall'] + ["male"] * (SCHEMA.num_categorical - 1)
    nums = ["1.5abc", "0x1A"] + ["2.0"] * (SCHEMA.num_numeric - 2)
    path = _edge_csv(tmp_path, [",".join(cats + nums)])
    got, _ = _both_paths(path, _tiny_prep())
    # Both garbage numerics impute to the median (=1.0 -> standardized 0).
    np.testing.assert_allclose(got.numeric[0, :2], 0.0, atol=1e-6)


def test_parity_underscore_numeric_literals(tmp_path):
    """Python's float() accepts underscore separators between digits
    (float("1_000") == 1000.0) and rejects every other placement; the
    native parser must agree cell-for-cell."""
    from mlops_tpu.schema import SCHEMA

    cats = ["male"] * SCHEMA.num_categorical
    pad = ["2.0"] * (SCHEMA.num_numeric - 4)
    valid = "1_000"        # -> 1000.0
    bad_lead = "_1"        # -> median
    bad_trail = "1_"       # -> median
    bad_double = "1__0"    # -> median
    path = _edge_csv(
        tmp_path,
        [",".join(cats + [valid, bad_lead, bad_trail, bad_double] + pad)],
    )
    got, want = _both_paths(path, _tiny_prep())
    # Underscored thousands parse like the plain literal would; the three
    # malformed ones impute to the median (=1.0 -> standardized 0).
    np.testing.assert_allclose(got.numeric[0, 1:4], 0.0, atol=1e-6)
    assert float("1_000") == 1000.0  # the contract being mirrored


def test_parity_duplicate_header_last_wins(tmp_path):
    from mlops_tpu.schema import SCHEMA

    names = [f.name for f in SCHEMA.categorical] + [
        f.name for f in SCHEMA.numeric
    ]
    header = ",".join(names) + ",credit_limit"  # duplicate numeric column
    row = ",".join(
        ["male"] * SCHEMA.num_categorical
        + ["7.0"] * SCHEMA.num_numeric
        + ["9.0"]
    )
    path = _edge_csv(tmp_path, [row], header=header)
    prep = _tiny_prep()
    got, want = _both_paths(path, prep)
    # Last occurrence (9.0) must win on both paths.
    j = [f.name for f in SCHEMA.numeric].index("credit_limit")
    assert got.numeric[0, j] == want.numeric[0, j] == 9.0 - 1.0


def test_parity_cr_only_line_endings(tmp_path):
    from mlops_tpu.schema import SCHEMA

    header = ",".join(f.name for f in SCHEMA.categorical) + "," + ",".join(
        f.name for f in SCHEMA.numeric
    )
    row = ",".join(["male"] * SCHEMA.num_categorical + ["3.0"] * SCHEMA.num_numeric)
    path = tmp_path / "cr.csv"
    path.write_bytes((header + "\r" + row + "\r" + row + "\r").encode())
    got = native.encode_csv_native(path, _tiny_prep())
    assert got.cat_ids.shape[0] == 2


def test_corrupt_labels_fail_fast_both_paths(tmp_path):
    from mlops_tpu.data.ingest import load_csv_columns
    from mlops_tpu.schema import SCHEMA

    header = (
        ",".join(f.name for f in SCHEMA.categorical)
        + ","
        + ",".join(f.name for f in SCHEMA.numeric)
        + f",{SCHEMA.target}"
    )
    row = ",".join(
        ["male"] * SCHEMA.num_categorical
        + ["1.0"] * SCHEMA.num_numeric
        + ["oops"]
    )
    path = _edge_csv(tmp_path, [row], header=header)
    with pytest.raises(ValueError, match="target"):
        native.encode_csv_native(path, _tiny_prep(), require_target=True)
    with pytest.raises(ValueError, match="target"):
        load_csv_columns(path, require_target=True)


def test_blank_labels_on_scoring_path_mean_unlabeled(tmp_path):
    """Scoring files keeping an empty target column score fine (labels
    -> None) on BOTH paths; only require_target fails fast."""
    from mlops_tpu.data.ingest import load_csv_columns
    from mlops_tpu.schema import SCHEMA

    header = (
        ",".join(f.name for f in SCHEMA.categorical)
        + ","
        + ",".join(f.name for f in SCHEMA.numeric)
        + f",{SCHEMA.target}"
    )
    rows = [
        ",".join(["male"] * SCHEMA.num_categorical + ["1.0"] * SCHEMA.num_numeric + ["1"]),
        ",".join(["male"] * SCHEMA.num_categorical + ["1.0"] * SCHEMA.num_numeric + [""]),
    ]
    path = _edge_csv(tmp_path, rows, header=header)
    prep = _tiny_prep()
    got = native.encode_csv_native(path, prep)
    assert got.labels is None and got.cat_ids.shape[0] == 2
    _, labels = load_csv_columns(path)
    assert labels is None


hypothesis = pytest.importorskip("hypothesis")  # not in the CI dep list


class TestParityFuzz:
    """Property-based parity: for ANY ascii CSV content — quoted cells,
    garbage numerics, short rows, empties — the native kernel must encode
    bit-identically to the Python path (the contract every other native
    test pins pointwise; hypothesis explores the space)."""

    from hypothesis import given, settings
    from hypothesis import strategies as st

    _ascii = st.text(
        alphabet=st.characters(min_codepoint=32, max_codepoint=126),
        max_size=10,
    )
    _cat_cell = st.one_of(
        st.sampled_from(["male", "female", "university", "", "other"]),
        _ascii,
    )
    _num_cell = st.one_of(
        st.floats(
            allow_nan=False, allow_infinity=False, min_value=-1e30, max_value=1e30
        ).map(repr),
        _ascii,
        st.just(""),
    )
    _row = st.builds(
        lambda cats, nums, keep: (cats + nums)[: max(1, keep)],
        st.lists(_cat_cell, min_size=9, max_size=9),
        st.lists(_num_cell, min_size=14, max_size=14),
        st.integers(min_value=1, max_value=23),  # short rows included
    )

    @given(rows=st.lists(_row, min_size=1, max_size=12))
    @settings(max_examples=30, deadline=None)
    def test_fuzzed_csv_parity(self, rows):
        import csv as _csv
        import io
        import tempfile

        from mlops_tpu.data.ingest import load_csv_columns
        from mlops_tpu.schema import SCHEMA

        buf = io.StringIO()
        writer = _csv.writer(buf)
        writer.writerow(list(SCHEMA.feature_names))
        writer.writerows(rows)
        with tempfile.NamedTemporaryFile(
            "w", suffix=".csv", delete=False
        ) as f:
            f.write(buf.getvalue())
            path = f.name

        try:
            prep = _tiny_prep()
            got = native.encode_csv_native(path, prep)
            columns, labels = load_csv_columns(path)
            want = prep.encode(columns, labels)
            np.testing.assert_array_equal(got.cat_ids, want.cat_ids)
            np.testing.assert_allclose(
                got.numeric, want.numeric, atol=1e-4, rtol=1e-5
            )
        finally:
            import os as _os

            _os.unlink(path)
