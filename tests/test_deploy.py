"""Deploy template validation (C19 / VERDICT r3 item 8).

No terraform binary ships in this image, so ``terraform validate`` can't
run in CI; this is a structural checker over the HCL + bootstrap templates
that fails on the defect classes a broken edit would introduce: unbalanced
blocks, references to undeclared variables, template placeholders nobody
supplies, dangling resource references, and firewall ports drifting from
the CommsConfig defaults the roles actually bind (reference topology:
``origin_repo/deploy/deploy.tf``).
"""

import re
from pathlib import Path

DEPLOY = Path(__file__).resolve().parent.parent / "deploy"


def _strip_comments_and_strings(text: str) -> str:
    """Remove # comments; keep string contents (brace balance includes
    interpolation braces, which HCL nests legally)."""
    return re.sub(r"#[^\n]*", "", text)


def test_hcl_braces_and_quotes_balanced():
    for tf in sorted(DEPLOY.glob("*.tf")):
        text = _strip_comments_and_strings(tf.read_text())
        assert text.count("{") == text.count("}"), f"{tf.name}: brace count"
        assert text.count('"') % 2 == 0, f"{tf.name}: unbalanced quotes"


def _main_and_vars():
    main = (DEPLOY / "main.tf").read_text()
    variables = (DEPLOY / "variables.tf").read_text()
    declared = set(re.findall(r'variable\s+"(\w+)"', variables))
    referenced = set(re.findall(r"\bvar\.(\w+)", main))
    return main, declared, referenced


def test_variables_declared_and_used():
    _, declared, referenced = _main_and_vars()
    undeclared = referenced - declared
    assert not undeclared, f"main.tf references undeclared {undeclared}"
    unused = declared - referenced
    assert not unused, f"variables.tf declares unused {unused}"


def test_templatefile_references_and_placeholders():
    """Every templatefile() call points at an existing script, supplies
    every ``${name}`` placeholder the script uses, and passes no unused
    keys.  Bash's own ``$(...)``/``\\$x`` forms don't collide: only bare
    ``${identifier}`` is a terraform placeholder."""
    main = (DEPLOY / "main.tf").read_text()
    calls = re.findall(
        r'templatefile\("\$\{path\.module\}/([\w.]+)",\s*\{(.*?)\}\s*\)',
        main, re.DOTALL)
    assert len(calls) >= 3, "learner/actor/evaluator templates expected"
    for fname, body in calls:
        script = DEPLOY / fname
        assert script.exists(), f"templatefile target missing: {fname}"
        keys = set(re.findall(r"(\w+)\s*=", body))
        placeholders = set(re.findall(r"\$\{(\w+)\}", script.read_text()))
        missing = placeholders - keys
        assert not missing, f"{fname}: unsupplied placeholders {missing}"
        unused = keys - placeholders
        assert not unused, f"{fname}: keys passed but never used {unused}"


def test_resource_references_resolve():
    main, _, _ = _main_and_vars()
    defined = {f"{t}.{n}" for t, n in
               re.findall(r'resource\s+"(\w+)"\s+"(\w+)"', main)}
    for ref in re.findall(
            r"\b(google_[a-z0-9_]+\.\w+)\.", main):
        assert ref in defined, f"dangling resource reference {ref}"


def test_firewall_ports_match_comms_config():
    """The opened ports must be exactly what the roles bind: chunk ingest,
    param PUB, barrier (CommsConfig defaults) + tensorboard.  The
    reference additionally opened the replay server's 51002/51003
    (deploy.tf:64-126); those MUST be gone — the replay server is
    dissolved."""
    from apex_tpu.config import CommsConfig

    main = (DEPLOY / "main.tf").read_text()
    m = re.search(r'ports\s*=\s*\[([^\]]*)\]', main)
    assert m, "no firewall ports list"
    ports = {int(p) for p in re.findall(r'"(\d+)"', m.group(1))}
    c = CommsConfig()
    assert {c.batch_port, c.param_port, c.barrier_port,
            c.status_port} <= ports
    assert 6006 in ports                     # tensorboard
    assert not ports & {51002, 51003}, \
        "replay-server ports resurrected on the LEARNER — the learner " \
        "hosts no replay sockets (the sharded service has its own rule)"


def test_replay_firewall_range_matches_comms_config():
    """The replay-host rule must open shard s's port (replay_port_base +
    s) for every supported shard, with actors AND the learner as sources
    (chunks in, pulls/write-backs in) — and the shard heartbeat path back
    to the learner must include apex-replay as a source."""
    from apex_tpu.config import CommsConfig

    main = (DEPLOY / "main.tf").read_text()
    m = re.search(
        r'"apex_replay_ports"(.*?)target_tags\s*=\s*\[([^\]]*)\]',
        main, re.DOTALL)
    assert m, "no apex_replay_ports firewall resource"
    body, targets = m.group(1), m.group(2)
    r = re.search(r'"(\d+)-(\d+)"', body)
    assert r, "replay firewall opens no port range"
    lo, hi = int(r.group(1)), int(r.group(2))
    c = CommsConfig()
    assert lo == c.replay_port_base
    assert hi >= c.replay_port_base + 15     # 16 shards per host
    assert "apex-replay" in targets
    src = re.search(r'source_tags\s*=\s*\[([^\]]*)\]', body).group(1)
    assert "apex-actor" in src and "apex-learner" in src
    # heartbeat return path: shard beats ride the learner's chunk port
    learner_rule = re.search(
        r'"apex_ports"(.*?)target_tags\s*=\s*\[([^\]]*)\]',
        main, re.DOTALL).group(1)
    learner_src = re.search(r'source_tags\s*=\s*\[([^\]]*)\]',
                            learner_rule).group(1)
    assert "apex-replay" in learner_src


def test_infer_firewall_and_heartbeat_path_match_comms_config():
    """The infer-host rule must open the serving shard range anchored at
    CommsConfig.infer_port (shard s binds infer_port + s, 16 per host
    like replay) with actors AND the serve-ctl controller as sources —
    and the return paths to the learner (param SUB on 52001, heartbeats
    on the chunk port) must include apex-infer as a source."""
    from apex_tpu.config import CommsConfig

    main = (DEPLOY / "main.tf").read_text()
    m = re.search(
        r'"apex_infer_port"(.*?)target_tags\s*=\s*\[([^\]]*)\]',
        main, re.DOTALL)
    assert m, "no apex_infer_port firewall resource"
    body, targets = m.group(1), m.group(2)
    r = re.search(r'"(\d+)-(\d+)"', body)
    assert r, "infer firewall opens no shard port range"
    lo, hi = int(r.group(1)), int(r.group(2))
    assert lo == CommsConfig().infer_port
    assert hi >= CommsConfig().infer_port + 15   # 16 shards per host
    assert "apex-infer" in targets
    src = re.search(r'source_tags\s*=\s*\[([^\]]*)\]', body).group(1)
    assert "apex-actor" in src and "apex-serve-ctl" in src
    learner_rule = re.search(
        r'"apex_ports"(.*?)target_tags\s*=\s*\[([^\]]*)\]',
        main, re.DOTALL).group(1)
    learner_src = re.search(r'source_tags\s*=\s*\[([^\]]*)\]',
                            learner_rule).group(1)
    assert "apex-infer" in learner_src


def test_provisioning_is_pinned_and_idempotent():
    """The Packer-analogue (VERDICT r4 item 7; reference:
    origin_repo/deploy/packer/ape_x_cpu.sh): one parametrized provision
    script bakes a PINNED env at /opt/apex-env, short-circuits on its
    marker so baked images and first-boot paths share it, and covers both
    accelerator flavors."""
    text = (DEPLOY / "provision.sh").read_text()
    assert re.search(r'"jax\[tpu\]==[\d.]+"', text), "jax[tpu] not pinned"
    assert re.search(r'"jax==[\d.]+"', text), "cpu jax not pinned"
    for pkg in ("flax", "optax", "numpy", "pyzmq"):
        assert re.search(rf'"{pkg}==[\d.]+"', text), f"{pkg} not pinned"
    assert "python3 -m venv" in text
    assert "exit 0" in text and "MARKER" in text, "no idempotence marker"
    assert "build-essential" in text, "native shm ring needs a compiler"


def test_pyproject_dependencies_pinned_in_provision():
    """Closes the ``--no-deps`` drift hole (ADVICE): every
    ``[project].dependencies`` name from pyproject.toml must appear in
    provision.sh's pip pin list, or a new runtime dep would install in
    dev environments but silently be absent from every baked fleet
    image."""
    pyproject = DEPLOY.parent / "pyproject.toml"
    try:
        import tomllib
        deps = tomllib.loads(pyproject.read_text())["project"]["dependencies"]
    except ModuleNotFoundError:                      # pre-3.11 fallback
        m = re.search(r"dependencies\s*=\s*\[(.*?)\]",
                      pyproject.read_text(), re.DOTALL)
        assert m, "no [project].dependencies in pyproject.toml"
        deps = re.findall(r'"([^"]+)"', m.group(1))
    assert deps, "pyproject declares no dependencies?"

    text = (DEPLOY / "provision.sh").read_text()
    pin_lines = [ln for ln in text.splitlines() if '"' in ln
                 and ("pip install" in ln or ln.strip().startswith('"'))]
    pins = " ".join(pin_lines)
    for dep in deps:
        name = re.split(r"[<>=!~;\[\s]", dep.strip(), 1)[0]
        assert re.search(rf'"{re.escape(name)}(\[\w+\])?[=">]', pins), \
            f"pyproject dependency {name!r} missing from provision.sh's " \
            f"pip pin list — baked images would ship without it"


def test_role_scripts_use_baked_env():
    """Every role bootstrap must run through the provisioned interpreter
    (baked image or first-boot fallback) — an unpinned system python is
    exactly the version skew the bake exists to kill."""
    for name, flavor in (("actor.sh", "cpu"), ("evaluator.sh", "cpu"),
                         ("replay.sh", "cpu"), ("infer.sh", "cpu"),
                         ("learner.sh", "tpu")):
        text = (DEPLOY / name).read_text()
        assert f"provision.sh {flavor}" in text, \
            f"{name}: no first-boot provisioning fallback"
        assert f".provisioned-{flavor}" in text, \
            f"{name}: fallback not gated on the idempotence marker"
        assert "/opt/apex-env/bin/python" in text, \
            f"{name}: role not launched from the baked env"
        for m in re.finditer(r"\S*pip install", text):
            assert m.group(0).startswith("/opt/apex-env/bin/pip"), \
                f"{name}: ad-hoc pip install outside the baked env: " \
                f"{m.group(0)!r}"


def test_packer_template_structure():
    """deploy/packer/apex_images.pkr.hcl: balanced HCL, the build block
    consumes the declared source, and the file provisioner ships the
    provision script that actually exists."""
    pkr = DEPLOY / "packer" / "apex_images.pkr.hcl"
    text = _strip_comments_and_strings(pkr.read_text())
    assert text.count("{") == text.count("}"), "packer HCL brace count"
    srcs = re.findall(r'source\s+"googlecompute"\s+"(\w+)"', text)
    assert srcs, "no googlecompute source"
    for s in srcs:
        assert f"source.googlecompute.{s}" in text, f"source {s} unused"
    m = re.search(r'source\s*=\s*"\$\{path\.root\}/([./\w]+)"',
                  pkr.read_text())
    assert m, "file provisioner missing"
    assert (pkr.parent / m.group(1)).resolve().exists(), \
        f"provisioner ships missing file {m.group(1)}"
    assert "provision.sh cpu" in pkr.read_text()


def test_fleet_image_variable_wired():
    """The baked image is selectable per fleet node (fleet_image), and the
    TPU VM — which cannot boot custom images — still provisions via its
    startup script."""
    main, declared, _ = _main_and_vars()
    assert "fleet_image" in declared
    # actors + evaluator + replay host + infer host
    assert main.count("image = var.fleet_image") == 4


def test_validate_binaries_if_available():
    """Run the real validators when the binaries exist (they don't in this
    image — the structural checks above are the CI fallback)."""
    import shutil
    import subprocess

    if shutil.which("packer"):
        p = subprocess.run(["packer", "validate", "-syntax-only",
                            str(DEPLOY / "packer")],
                           capture_output=True, text=True)
        assert p.returncode == 0, p.stderr
    if shutil.which("terraform"):
        # validate needs the provider schema: init without any backend
        p = subprocess.run(["terraform", f"-chdir={DEPLOY}", "init",
                            "-backend=false", "-input=false"],
                           capture_output=True, text=True)
        assert p.returncode == 0, p.stderr
        p = subprocess.run(["terraform", f"-chdir={DEPLOY}", "validate"],
                           capture_output=True, text=True)
        assert p.returncode == 0, p.stderr


def test_bootstrap_scripts_use_host_supervisor():
    """Crashed remote roles must respawn (VERDICT r3 weak #6): the actor
    and evaluator bootstraps launch through the rate-limited,
    respawn-budgeted host supervisor (apex_tpu.fleet.supervise — the
    ActorPool respawn semantics for whole processes), which pairs with
    the roles' park/rejoin path.  The old inline ``while true`` loops
    must stay gone: they had no budget window and no jitter."""
    for name in ("actor.sh", "evaluator.sh", "replay.sh", "infer.sh"):
        text = (DEPLOY / name).read_text()
        assert "apex_tpu.fleet.supervise" in text, \
            f"{name}: role not launched under the host supervisor"
        assert "--max-respawns" in text and "--window" in text, \
            f"{name}: supervisor launched without a respawn budget"
        assert "/opt/apex-env/bin/python -m apex_tpu.fleet.supervise" \
            in text, f"{name}: supervisor not run from the baked env"
        assert "while true" not in text, \
            f"{name}: bare respawn loop resurrected alongside the " \
            f"supervisor"
