"""The persistent compile cache is placed from outside or at one fixed path,
and the entry points that turn it on fail loudly when a suite errors."""

import pathlib

from repro import compile_cache as CC


def test_environment_places_the_cache():
    assert CC.cache_dir({CC.ENV_VAR: "/elsewhere"}) is None


def test_default_is_one_fixed_ignored_path_in_the_checkout():
    root = pathlib.Path(__file__).resolve().parents[1]
    assert CC.cache_dir({}) == str(root / ".jax_cache")
    assert CC.cache_dir({}) == CC.cache_dir({CC.ENV_VAR: ""})
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_tpu_logs_are_off_unless_placed():
    env = {}
    assert CC.disable_tpu_logs(env) == "disabled"
    assert env == {CC.TPU_LOG_VAR: "disabled"}
    env = {CC.TPU_LOG_VAR: "/elsewhere"}
    assert CC.disable_tpu_logs(env) == "/elsewhere"
    assert env == {CC.TPU_LOG_VAR: "/elsewhere"}


def test_benchmark_harness_exits_nonzero_on_a_suite_error(monkeypatch,
                                                         capsys):
    import benchmarks.run as br

    monkeypatch.setattr(br, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(br, "disable_tpu_logs", lambda: None)
    assert br.main(["--only", "no_such_suite"]) == 1
    assert "no_such_suite_suite_error" in capsys.readouterr().out
