"""The cfg parser must accept the reference's raft.cfg byte-for-byte."""

import pathlib

import pytest

from raft_tla_tpu.utils.cfgparse import parse_cfg, load_cfg

REF_CFG = pathlib.Path("/root/reference/raft.cfg")
if not REF_CFG.exists():        # not mounted here: the vendored copy
    REF_CFG = pathlib.Path(__file__).parent / "fixtures" / "raft.cfg"


def test_reference_cfg_parses():
    cfg = load_cfg(str(REF_CFG))
    assert cfg.specification == "Spec"
    assert cfg.invariants == ["NoTwoLeaders"]
    assert cfg.server_names() == ["s1", "s2", "s3"]
    assert cfg.value_names() == ["v1", "v2"]
    # Model values (raft.cfg:8-15)
    assert cfg.constants["Follower"] == "Follower"
    assert cfg.constants["Nil"] == "Nil"
    assert cfg.constants["AppendEntriesResponse"] == "AppendEntriesResponse"


def test_constraint_and_plural_stanzas():
    cfg = parse_cfg(
        """
SPECIFICATION Spec
INVARIANTS A B
CONSTRAINT StateConstraint
CONSTANTS
    Server = {s1, s2}
    Nil = Nil
"""
    )
    assert cfg.invariants == ["A", "B"]
    assert cfg.constraints == ["StateConstraint"]
    assert cfg.server_names() == ["s1", "s2"]


def test_comments_stripped():
    cfg = parse_cfg("CONSTANTS\n  Server = {a, b, c} \\* three nodes\n")
    assert cfg.server_names() == ["a", "b", "c"]


def test_junk_rejected():
    with pytest.raises(ValueError):
        parse_cfg("NOT_A_STANZA foo\n")
