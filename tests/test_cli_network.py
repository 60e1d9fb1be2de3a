"""nwo-style integration: REAL processes launched via the fabric-tpu
CLI — cryptogen → configtxgen → orderer + ccaas chaincode + 2 peers →
gateway invoke/query → discovery → ledgerutil verify (the
integration/nwo harness pattern: declarative network, real binaries,
localhost ports)."""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHANNEL = "clichan"
CC = "clicc"


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _cli_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return env


def _cli(*args, **kw):
    return subprocess.run(
        [sys.executable, "-m", "fabric_tpu.cli", *args],
        cwd=REPO, env=_cli_env(), capture_output=True, text=True,
        timeout=kw.pop("timeout", 120), **kw,
    )


def _spawn(*args):
    return subprocess.Popen(
        [sys.executable, "-m", "fabric_tpu.cli", *args],
        cwd=REPO, env=_cli_env(),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _wait_port(port, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            s = socket.create_connection(("127.0.0.1", port), 1)
            s.close()
            return True
        except OSError:
            time.sleep(0.3)
    return False


@pytest.mark.slow
def test_cli_network(tmp_path):
    crypto = str(tmp_path / "crypto")
    res = _cli("cryptogen", "--org", "Org1MSP:org1.example.com",
               "--org", "Org2MSP:org2.example.com",
               "--org", "OrdererMSP:ord.example.com",
               "--orderers", "1", "--output", crypto)
    assert res.returncode == 0, res.stderr
    org1 = f"{crypto}/org1.example.com"
    org2 = f"{crypto}/org2.example.com"
    ordorg = f"{crypto}/ord.example.com"

    # one trusted TLS-CA bundle across the network: every listener
    # demands a client cert and every dial presents one (mutual TLS)
    ca_bundle = str(tmp_path / "tls-ca-bundle.pem")
    with open(ca_bundle, "wb") as bf:
        for od in (org1, org2, ordorg):
            with open(f"{od}/tlsca/tlsca-cert.pem", "rb") as cf:
                bf.write(cf.read())

    def tls_cfg(org_dir, node):
        tdir = f"{org_dir}/nodes/{node}/tls"
        return {"cert": f"{tdir}/server.pem", "key": f"{tdir}/key.pem",
                "ca": ca_bundle}

    profile = {
        "channel": CHANNEL,
        "application_orgs": [
            {"msp_id": "Org1MSP", "dir": org1},
            {"msp_id": "Org2MSP", "dir": org2},
        ],
        # orderer org in the genesis config: peers verify every
        # delivered block's signature against BlockValidation
        "orderer_orgs": [{"msp_id": "OrdererMSP", "dir": ordorg}],
        "max_message_count": 1, "batch_timeout_ms": 100,
    }
    prof_path = str(tmp_path / "profile.json")
    with open(prof_path, "w") as f:
        json.dump(profile, f)
    genesis = str(tmp_path / "genesis.block")
    res = _cli("configtxgen", "--profile", prof_path, "--output", genesis)
    assert res.returncode == 0, res.stderr

    cc_port = _free_port()
    ord_port = _free_port()
    p1_port, p2_port = _free_port(), _free_port()
    ops_port = _free_port()

    ord_cfg = {
        "id": "o0", "data_dir": str(tmp_path / "o0"), "port": ord_port,
        "cluster": {"o0": ["127.0.0.1", ord_port]},
        "max_message_count": 1, "batch_timeout_s": 0.1,
        "msp_id": "OrdererMSP",
        "msp_dir": f"{ordorg}/nodes/orderer0.ord.example.com/msp",
        "tls": tls_cfg(ordorg, "orderer0.ord.example.com"),
        "channels": [{"name": CHANNEL, "genesis": genesis}],
    }

    def peer_cfg(pid, port, org_dir, msp_id, other_port, other_msp):
        return {
            "id": pid, "data_dir": str(tmp_path / pid), "port": port,
            "msp_id": msp_id,
            "msp_dir": f"{org_dir}/nodes/peer0.{os.path.basename(org_dir)}/msp",
            "tls": tls_cfg(org_dir, f"peer0.{os.path.basename(org_dir)}"),
            "org_msps": [org1, org2],
            # NO static chaincode registration: the peers must resolve
            # CC from the INSTALLED package bound by their org's
            # approval (the install/package flow under test)
            "peers": [{"msp_id": other_msp, "host": "127.0.0.1",
                       "port": other_port}],
            "channels": [{
                "name": CHANNEL, "genesis": genesis,
                "orderers": [["127.0.0.1", ord_port]],
            }],
            "operations_port": ops_port if pid == "p1" else None,
        }

    cfgs = {
        "orderer": ord_cfg,
        "p1": peer_cfg("p1", p1_port, org1, "Org1MSP", p2_port, "Org2MSP"),
        "p2": peer_cfg("p2", p2_port, org2, "Org2MSP", p1_port, "Org1MSP"),
    }
    for name, cfg in cfgs.items():
        with open(tmp_path / f"{name}.json", "w") as f:
            json.dump(cfg, f)

    procs = []
    try:
        procs.append(_spawn("chaincode", "--name", CC, "--port", str(cc_port)))
        procs.append(_spawn("orderer", "--config", str(tmp_path / "orderer.json")))
        assert _wait_port(cc_port) and _wait_port(ord_port)
        procs.append(_spawn("peer", "--config", str(tmp_path / "p1.json")))
        procs.append(_spawn("peer", "--config", str(tmp_path / "p2.json")))
        assert _wait_port(p1_port) and _wait_port(p2_port)

        user_msp = f"{org1}/users/User1@org1.example.com/msp"
        cli_tls = ("--tls-ca", ca_bundle,
                   "--tls-cert",
                   f"{org1}/nodes/peer0.org1.example.com/tls/server.pem",
                   "--tls-key",
                   f"{org1}/nodes/peer0.org1.example.com/tls/key.pem")

        # chaincode package + install on BOTH peers (package.go /
        # install.go): the approve step then binds the package id
        pkg_path = str(tmp_path / "kv.tgz")
        res = _cli("ccpackage", "--label", "kv_1",
                   "--address", f"127.0.0.1:{cc_port}",
                   "--output", pkg_path)
        assert res.returncode == 0, res.stdout + res.stderr
        pkg_id = json.loads(res.stdout.strip().splitlines()[-1])["package_id"]
        for pp in (p1_port, p2_port):
            res = _cli(*cli_tls, "ccinstall", "--port", str(pp),
                       "--package", pkg_path)
            assert res.returncode == 0, res.stdout + res.stderr
            out = json.loads(res.stdout.strip().splitlines()[-1])
            assert out["status"] == 200 and out["package_id"] == pkg_id
        res = _cli(*cli_tls, "ccqueryinstalled", "--port", str(p1_port))
        assert res.returncode == 0, res.stdout + res.stderr
        assert json.loads(res.stdout.strip().splitlines()[-1])[
            "installed"] == [{"package_id": pkg_id, "label": "kv_1"}]

        # chaincode lifecycle: approve from EACH org (binding the
        # installed package id), then commit — the reference's
        # approve/commit flow driven through the gateway
        spec = json.dumps({"policy": {"ref": "Endorsement"},
                           "package_id": pkg_id})
        for msp_id, org_dir in (("Org1MSP", org1), ("Org2MSP", org2)):
            u = f"{org_dir}/users/User1@{os.path.basename(org_dir)}/msp"
            res = _cli(
                *cli_tls, "invoke", "--port", str(p1_port), "--channel", CHANNEL,
                "--chaincode", "_lifecycle", "--msp-dir", u,
                "--msp-id", msp_id, "approve", CC, "1", spec, timeout=600,
            )
            assert res.returncode == 0, res.stdout + res.stderr
            assert json.loads(res.stdout.strip().splitlines()[-1])["code"] == 0
        res = _cli(
            *cli_tls, "invoke", "--port", str(p1_port), "--channel", CHANNEL,
            "--chaincode", "_lifecycle", "--msp-dir", user_msp,
            "--msp-id", "Org1MSP", "commit", CC, "1", spec, timeout=300,
        )
        assert res.returncode == 0, res.stdout + res.stderr
        assert json.loads(res.stdout.strip().splitlines()[-1])["code"] == 0

        # invoke through the gateway CLI (endorse across BOTH orgs per
        # the committed definition's Endorsement-ref policy)
        res = _cli(
            *cli_tls, "invoke", "--port", str(p1_port), "--channel", CHANNEL,
            "--chaincode", CC, "--msp-dir", user_msp, "--msp-id", "Org1MSP",
            "put", "city", "lucerne", timeout=600,
        )
        assert res.returncode == 0, res.stdout + res.stderr
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert out["code_name"] == "VALID", out

        res = _cli(
            *cli_tls, "query", "--port", str(p2_port), "--channel", CHANNEL,
            "--chaincode", CC, "--msp-dir", user_msp, "--msp-id", "Org1MSP",
            "get", "city", timeout=300,
        )
        assert res.returncode == 0, res.stdout + res.stderr
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert out["payload"] == "lucerne", out

        res = _cli(*cli_tls, "discover", "--port", str(p1_port),
                   "--channel", CHANNEL,
                   "--query", "endorsers", "--chaincode", CC)
        desc = json.loads(res.stdout.strip().splitlines()[-1])
        assert desc["status"] == 200
        assert {"Org1MSP": 1, "Org2MSP": 1} in desc["descriptor"]["layouts"]

        # operations surface of a real peer process
        import urllib.request

        with urllib.request.urlopen(
            f"http://127.0.0.1:{ops_port}/healthz", timeout=5
        ) as r:
            assert json.loads(r.read())["status"] == "OK"
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ops_port}/metrics", timeout=5
        ) as r:
            assert b"ledger_blockchain_height" in r.read()
    finally:
        for p in procs:
            p.send_signal(signal.SIGINT)
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()

    # offline forensics on the stopped peers' ledgers
    res = _cli("ledgerutil", "verify", str(tmp_path / "p1" / CHANNEL))
    assert res.returncode == 0, res.stdout + res.stderr
    assert json.loads(res.stdout)["ok"]
    res = _cli("ledgerutil", "compare",
               str(tmp_path / "p1" / CHANNEL), str(tmp_path / "p2" / CHANNEL))
    assert res.returncode == 0, res.stdout
    assert json.loads(res.stdout)["identical"]
