"""Benchmark aggregator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Each module exposes ``run(quick) -> dict``; failures are collected and the
exit code reflects overall success.
"""

import argparse
import sys
import time
import traceback

MODULES = [
    ("tab_s2_ramps", "Tab. S1/S2 + Fig. 2d/2e ramp tables"),
    ("fig3_calibration", "Fig. 3a / Fig. S7 calibration INL"),
    ("fig3b_vread", "Fig. 3b V_read robustness"),
    ("s11_redundancy", "Supp. S11 redundancy"),
    ("s12_nonmonotonic", "Supp. S12 GELU/Swish split"),
    ("tab_s5_macro", "Tab. S3-S5 KWS macro costs"),
    ("tab_s9_nlp", "Tab. S6-S9 NLP macro costs"),
    ("tab_s12_s17_system", "Tab. S10-S17 system costs"),
    ("tab1_comparison", "Tab. 1 / Fig. 4e accelerator comparison"),
    ("tab2_adc", "Tab. 2 ADC comparison"),
    ("fig4d_kws", "Fig. 4d KWS accuracy vs bits"),
    ("fig5c_ptb", "Fig. 5c char-LM BPC vs bits"),
    ("s13_drift", "Supp. S13 drift"),
    ("device_sweep", "repro.core.device preset sweep (drift/redundancy)"),
    ("ir_sweep", "IR-drop correction vs exact nodal solve + bank INL"),
    ("bank_sweep", "threshold-bank sweep (INL/accuracy vs col-tile count)"),
    ("recal_schedule", "serving-lifetime re-calibration schedule sweep"),
    ("fleet_sweep", "fleet serving sweep (N chips x capacity floor)"),
    ("kernel_bench", "kernel microbench"),
    ("kernel_tune", "per-shape kernel block autotune sweep + digests"),
    ("backend_parity", "ref-vs-pallas backend parity + throughput"),
    ("dist_scaling", "repro.dist device-count scaling sweep"),
    ("roofline_report", "dry-run roofline table"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    results, failures = {}, []
    for name, desc in MODULES:
        if args.only and args.only != name:
            continue
        print(f"\n##### {name}: {desc} #####", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            results[name] = mod.run(quick=not args.full)
            print(f"##### {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:   # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
            print(f"##### {name} FAILED: {e}", flush=True)

    print("\n================ benchmark summary ================")
    for name, _ in MODULES:
        if args.only and args.only != name:
            continue
        status = "FAIL" if name in failures else "ok"
        print(f"  {name:22} {status}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
