#!/usr/bin/env python3
"""By hand, on the chip: controls of ``trinity-mini.long-context``'s ``correct``.

``bench/run.py`` leaves a run's probes (what the timed path served) in
``chiprun_out/bench/<cell>/seed<n>-trace<t>/probes.json``. This sends the same
probes through the harness's own comparison, ``bench/reference.py`` as it
stands, with the configuration's file changed in ONE mechanism (ISSUE 41's
controls): (a) the window dropped from the sliding layers, (b) the attention's
output gate dropped, (c) the selection bias dropped from the router's selection,
(d) the shared expert dropped, (e) rotary applied in the global layers. A
control has to come out NOT ok (exit code 1 from the child): if it reads ok, the
comparison cannot see the mechanism (PERF.md section 6, PR 41). ``sound`` runs
the file unchanged, which has to read ok.

    python3 hack/trinity_correct_control.py <run's out_dir> [--controls a b c d e] [--rehearse-cpu]

Prints one line of JSON a control: the child's verdict, its largest and mean
difference and each probe's. Exit code 0 when every control read not ok (and
``sound``, where asked for, ok). ``--rehearse-cpu`` changes the rehearsal's
model block instead and passes the flag on to the child.
"""
import argparse
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CONFIG = ROOT / "bench" / "configs" / "trinity-mini-int8.json"
CONTROLS = {
    "sound": {},
    "a": {"sliding_window": 10 ** 9},
    "b": {"assumed_mechanisms": {"attention_output_gate": False}},
    "c": {"assumed_mechanisms": {"selection_bias": "none"}},
    "c_weights": {"assumed_mechanisms": {"selection_bias": "weights"}},
    "d": {"num_shared_experts": 0},
    "e": {"assumed_mechanisms": {"global_layers_rope": True}},
}


def changed(model: dict, control: dict) -> dict:
    out = {**model, **control}
    if "assumed_mechanisms" in control:
        out["assumed_mechanisms"] = {**model.get("assumed_mechanisms", {}), **control["assumed_mechanisms"]}
    return out


def run(config_file: Path, controls: dict, default: list, doc: str, expected_ok: tuple = ("sound",)) -> int:
    """The command line over one configuration's ``controls`` (another
    configuration's script hands in its own: hack/lfm2_correct_control.py);
    ``expected_ok`` names those that have to read ok, every other not ok."""
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("out_dir", type=Path)
    ap.add_argument("--controls", nargs="+", default=default, choices=list(controls))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    config = json.loads(config_file.read_text())
    as_wanted = True
    for name in args.controls:
        if args.rehearse_cpu:
            variant_config = {**config, "rehearsal": {**config["rehearsal"],
                                                      "model": changed(config["rehearsal"]["model"], controls[name])}}
        else:
            variant_config = changed(config, controls[name])
        variant = args.out_dir / f"control-{name}.json"
        variant.write_text(json.dumps(variant_config))
        out = subprocess.run([sys.executable, str(ROOT / "bench" / "reference.py"), str(variant),
                              str(args.out_dir / "probes.json")] + (["--rehearse-cpu"] if args.rehearse_cpu else []),
                             capture_output=True, text=True, timeout=float(config["reference_limit_s"]))
        lines = out.stdout.strip().splitlines()
        result = json.loads(lines[-1]) if lines else {"error": out.stderr[-1500:]}
        print(json.dumps({"control": name, "rc": out.returncode, "ok": result.get("ok"),
                          "max_abs_diff": result.get("max_abs_diff"), "mean_abs_diff": result.get("mean_abs_diff"),
                          "probes": [(p.get("prompt_tokens"), p.get("max_abs_diff"), p.get("mean_abs_diff"))
                                     for p in result.get("probes", [])],
                          "weights_s": result.get("weights_s"), "seconds": result.get("seconds"),
                          "peak_bytes": (result.get("memory") or {}).get("peak_bytes"),
                          "error": result.get("error")}), flush=True)
        wanted = (0, True) if name in expected_ok else (1, False)
        as_wanted = as_wanted and (out.returncode, result.get("ok")) == wanted
    return 0 if as_wanted else 1


if __name__ == "__main__":
    sys.exit(run(CONFIG, CONTROLS, ["a", "b", "c", "d", "e"], __doc__))
