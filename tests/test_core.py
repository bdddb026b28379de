"""Core layer tests: schema parsing, properties config, columnar ingest."""

import json
import textwrap

import numpy as np
import pytest

from avenir_tpu.core.config import (
    JobConfig,
    MissingConfigError,
    parse_properties_string,
)
from avenir_tpu.core.dataset import Dataset
from avenir_tpu.core.schema import FeatureSchema

CHURN_SCHEMA = {
    "fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        {
            "name": "minUsed",
            "ordinal": 1,
            "dataType": "categorical",
            "cardinality": ["low", "med", "high", "overage"],
            "feature": True,
        },
        {
            "name": "holdTime",
            "ordinal": 2,
            "dataType": "int",
            "feature": True,
            "min": 0,
            "max": 600,
            "bucketWidth": 60,
        },
        {
            "name": "income",
            "ordinal": 3,
            "dataType": "double",
            "feature": True,
        },
        {
            "name": "status",
            "ordinal": 4,
            "dataType": "categorical",
            "cardinality": ["open", "closed"],
        },
    ]
}

CSV = textwrap.dedent(
    """\
    a1,low,30,55.5,open
    a2,high,120,80.0,closed
    a3,overage,599,21.0,closed
    a4,med,0,44.2,open
    """
)


@pytest.fixture
def schema():
    return FeatureSchema.from_json(CHURN_SCHEMA)


@pytest.fixture
def ds(schema):
    return Dataset.from_csv(CSV, schema)


class TestSchema:
    def test_roles(self, schema):
        assert schema.id_field.name == "id"
        assert [f.name for f in schema.feature_fields] == [
            "minUsed",
            "holdTime",
            "income",
        ]
        # implicit class attribute: trailing non-feature categorical
        assert schema.class_field.name == "status"
        assert schema.num_classes() == 2
        assert schema.class_values() == ["open", "closed"]

    def test_bins(self, schema):
        f = schema.field_by_name("minUsed")
        assert f.num_bins() == 4
        assert f.encode_value("overage") == 3
        assert f.decode_value(1) == "med"
        h = schema.field_by_name("holdTime")
        assert h.num_bins() == 11  # 600/60 + 1
        assert h.encode_value("0") == 0
        assert h.encode_value("119") == 1
        # unbinned double has no dense state
        assert schema.field_by_name("income").num_bins() == 0

    def test_roundtrip(self, schema, tmp_path):
        p = tmp_path / "s.json"
        schema.save(str(p))
        again = FeatureSchema.from_file(str(p))
        assert json.dumps(again.to_json(), sort_keys=True) == json.dumps(
            schema.to_json(), sort_keys=True
        )

    def test_explicit_class_attr(self):
        obj = {
            "fields": [
                {
                    "name": "y",
                    "ordinal": 0,
                    "dataType": "categorical",
                    "cardinality": ["a", "b"],
                    "classAttribute": True,
                },
                {
                    "name": "x",
                    "ordinal": 1,
                    "dataType": "categorical",
                    "cardinality": ["p", "q"],
                    "feature": True,
                },
            ]
        }
        s = FeatureSchema.from_json(obj)
        assert s.class_field.name == "y"


class TestConfig:
    PROPS = textwrap.dedent(
        """\
        # shared
        field.delim.regex=,
        debug.on=true
        num.reducer=1
        nen.top.match.count=5
        nen.kernel.function=none
        nen.class.condtion.weighted=true
        dtb.max.depth.limit=2
        dtb.min.info.gain.limit=
        costs=2,5.5
        """
    )

    def test_prefix_resolution(self):
        cfg = JobConfig(parse_properties_string(self.PROPS), prefix="nen")
        assert cfg.get_int("top.match.count") == 5
        assert cfg.get("kernel.function") == "none"
        assert cfg.get_bool("class.condtion.weighted") is True
        # falls back to shared unprefixed key
        assert cfg.get_int("num.reducer") == 1
        assert cfg.debug_on is True

    def test_empty_value_is_missing(self):
        cfg = JobConfig(parse_properties_string(self.PROPS), prefix="dtb")
        assert cfg.get_float("min.info.gain.limit") is None
        assert cfg.get_int("max.depth.limit") == 2

    def test_assert_raises(self):
        cfg = JobConfig(parse_properties_string(self.PROPS), prefix="nen")
        with pytest.raises(MissingConfigError):
            cfg.assert_int("nonexistent.key")

    def test_lists(self):
        cfg = JobConfig(parse_properties_string(self.PROPS))
        assert cfg.get_float_list("costs") == [2.0, 5.5]

    def test_scoped(self):
        cfg = JobConfig(parse_properties_string(self.PROPS), prefix="nen")
        assert cfg.scoped("dtb").get_int("max.depth.limit") == 2


class TestHocon:
    """HOCON loader for the Spark-surface config (resource/atmTrans.conf,
    MarkovStateTransitionModel.scala:43-46)."""

    CONF = textwrap.dedent(
        """\
        // spark job blocks
        stateTransitionRate {
            field.delim.in = ","
            key.field.ordinals = [0]
            state.values = ["10", "20", "30"]
            rate.time.unit = "day"
            trans.rate.output.precision = 9
            debug.on = false
        }
        contTimeStateTransitionStats {
            state.values = ["F", "P", "L"]
            time.horizon = 4
            state.trans.file.path="file:///tmp/tra"
            target.states = ["L"]
            nested {
                inner.key = 7
            }
        }
        """
    )

    def test_blocks_and_values(self, tmp_path):
        from avenir_tpu.core.config import load_hocon

        p = tmp_path / "jobs.conf"
        p.write_text(self.CONF)
        blocks = load_hocon(str(p))
        assert set(blocks) == {"stateTransitionRate",
                               "contTimeStateTransitionStats"}
        str_blk = blocks["stateTransitionRate"]
        assert str_blk["key.field.ordinals"] == "0"
        assert str_blk["state.values"] == "10,20,30"
        assert str_blk["rate.time.unit"] == "day"
        cts = blocks["contTimeStateTransitionStats"]
        assert cts["state.trans.file.path"] == "file:///tmp/tra"
        assert cts["nested.inner.key"] == "7"

    def test_jobconfig_over_block(self, tmp_path):
        p = tmp_path / "jobs.conf"
        p.write_text(self.CONF)
        cfg = JobConfig.from_hocon(str(p), "contTimeStateTransitionStats",
                                   prefix="cts")
        assert cfg.get_list("state.values") == ["F", "P", "L"]
        assert cfg.get_float("time.horizon") == 4.0
        assert cfg.get_list("target.states") == ["L"]
        with pytest.raises(MissingConfigError):
            JobConfig.from_hocon(str(p), "noSuchJob")

    def test_parses_actual_reference_conf(self):
        import os

        from avenir_tpu.core.config import load_hocon

        ref = "/root/reference/resource/atmTrans.conf"
        if not os.path.exists(ref):
            pytest.skip("reference tree not mounted")
        blocks = load_hocon(ref)
        cts = blocks["contTimeStateTransitionStats"]
        assert cts["state.values"].split(",") == [
            "10", "20", "30", "40", "50", "60", "70", "80", "90", "100"]
        assert cts["state.trans.stat"] == "stateDwellTime"
        assert blocks["stateTransitionRate"]["rate.time.unit"] == "day"

    def test_malformed_raises(self, tmp_path):
        from avenir_tpu.core.config import load_hocon

        p = tmp_path / "bad.conf"
        p.write_text("jobA {\n key = 1\n")
        with pytest.raises(ValueError, match="unclosed"):
            load_hocon(str(p))
        p.write_text("stray.key = 1\n")
        with pytest.raises(ValueError, match="outside a job block"):
            load_hocon(str(p))


class TestDataset:
    def test_columns(self, ds):
        assert len(ds) == 4
        assert list(ds.ids()) == ["a1", "a2", "a3", "a4"]
        np.testing.assert_array_equal(ds.labels(), [0, 1, 1, 0])

    def test_feature_codes(self, ds):
        codes, bins = ds.feature_codes()
        assert bins == [4, 11]
        np.testing.assert_array_equal(codes[:, 0], [0, 2, 3, 1])  # minUsed
        np.testing.assert_array_equal(codes[:, 1], [0, 2, 9, 0])  # holdTime buckets

    def test_feature_matrix(self, ds):
        m = ds.feature_matrix()
        assert m.shape == (4, 2)  # holdTime + income
        np.testing.assert_allclose(m[:, 1], [55.5, 80.0, 21.0, 44.2], rtol=1e-6)

    def test_unknown_categorical_raises(self, schema):
        with pytest.raises(ValueError, match="cardinality"):
            Dataset.from_csv("a1,BOGUS,30,55.5,open\n", schema)

    def test_take(self, ds):
        sub = ds.take(np.array([2, 0]))
        assert list(sub.ids()) == ["a3", "a1"]
        np.testing.assert_array_equal(sub.labels(), [1, 0])


def test_rich_attribute_schema_wrapper():
    """sifarish rich-schema layout (resource/elearnActivity.json): entity
    wrapper + distAlgorithm, consumed by the similarity stage."""
    from avenir_tpu.core.schema import FeatureSchema

    s = FeatureSchema.from_string("""
    {
      "distAlgorithm": "euclidean",
      "numericDiffThreshold": 0.2,
      "entity": {
        "name": "studentActivity",
        "fields": [
          {"name": "id", "ordinal": 0, "id": true, "dataType": "string"},
          {"name": "score", "ordinal": 1, "dataType": "int",
           "feature": true, "min": 0, "max": 100},
          {"name": "status", "ordinal": 2, "dataType": "categorical",
           "cardinality": ["fail", "pass"]}
        ]
      }
    }""")
    assert s.dist_algorithm == "euclidean"
    assert s.entity_name == "studentActivity"
    assert s.class_field.name == "status"
    assert len(s.feature_fields) == 1


def test_schema_rejects_unknown_layout():
    from avenir_tpu.core.schema import FeatureSchema
    import pytest as _pytest

    with _pytest.raises(ValueError, match="fields"):
        FeatureSchema.from_json({"something": []})


def test_parses_actual_reference_schemas():
    """When the reference checkout is present, every schema JSON it ships
    must load (the verbatim-compat surface of SURVEY §5)."""
    import glob
    import pytest as _pytest

    from avenir_tpu.core.schema import FeatureSchema

    files = sorted(glob.glob("/root/reference/resource/*.json"))
    if not files:
        _pytest.skip("reference checkout not present")
    for p in files:
        s = FeatureSchema.from_file(p)
        assert len(s.fields) > 0, p


@pytest.mark.parametrize("engine", ["python", "native"])
def test_undeclared_categorical_discovers_vocab(engine):
    """Categorical without declared cardinality (elearnActivity.json's
    status field): vocabulary discovered from data, consistent across
    splits parsed with the same schema, growable on unseen values."""
    from avenir_tpu.core.dataset import Dataset
    from avenir_tpu.core.schema import FeatureSchema

    s = FeatureSchema.from_json({"fields": [
        {"name": "x", "ordinal": 0, "dataType": "double", "feature": True},
        {"name": "status", "ordinal": 1, "dataType": "categorical"},
    ]})
    status = s.field_by_name("status")
    ds1 = Dataset.from_csv("1,pass\n2,fail\n3,pass\n", s, engine=engine)
    assert status.cardinality == ["fail", "pass"]
    assert status.discovered_cardinality
    np.testing.assert_array_equal(ds1.labels(), [1, 0, 1])
    assert ds1.labels().dtype == np.int32
    # a later split with only one value keeps the same codes
    ds2 = Dataset.from_csv("4,pass\n", s, engine=engine)
    np.testing.assert_array_equal(ds2.labels(), [1])
    # and an unseen value extends instead of raising
    ds3 = Dataset.from_csv("5,hold\n", s, engine=engine)
    assert status.cardinality == ["fail", "pass", "hold"]
    np.testing.assert_array_equal(ds3.labels(), [2])
    # several unseen values at once go behind the known ones, sorted
    # among themselves, and a short row is the empty token
    ds4 = Dataset.from_csv("6,zeta\n7\n8,alpha\n9,fail\n", s, engine=engine)
    assert status.cardinality == ["fail", "pass", "hold", "", "alpha", "zeta"]
    np.testing.assert_array_equal(ds4.labels(), [5, 3, 4, 0])


def test_implicit_feature_roles_without_flags():
    """Rich schemas mark only id/class roles; everything else is a feature
    (the convention the sifarish similarity stage applies)."""
    from avenir_tpu.core.schema import FeatureSchema

    s = FeatureSchema.from_json({"fields": [
        {"name": "id", "ordinal": 0, "id": True, "dataType": "string"},
        {"name": "a", "ordinal": 1, "dataType": "int", "min": 0, "max": 9},
        {"name": "b", "ordinal": 2, "dataType": "double"},
        {"name": "status", "ordinal": 3, "dataType": "categorical",
         "cardinality": ["n", "y"]},
    ]})
    assert [f.name for f in s.feature_fields] == ["a", "b"]
    assert s.class_field.name == "status"
    # explicit flags still win
    s2 = FeatureSchema.from_json({"fields": [
        {"name": "a", "ordinal": 0, "dataType": "int", "feature": True},
        {"name": "b", "ordinal": 1, "dataType": "int"},
        {"name": "status", "ordinal": 2, "dataType": "categorical",
         "cardinality": ["n", "y"]},
    ]})
    assert [f.name for f in s2.feature_fields] == ["a"]
