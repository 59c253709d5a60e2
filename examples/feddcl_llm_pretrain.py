"""FedDCL's outer tier applied to LLM pretraining: 4 silos (DC-server
groups), H=4 local steps per FedAvg round, reduced llama backbone, synthetic
non-IID token streams — the paper's communication schedule as a first-class
training feature (DESIGN.md §3).

  PYTHONPATH=src python examples/feddcl_llm_pretrain.py --steps 80
"""
import argparse

from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=80)
    ap.add_argument("--silos", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=4)
    args = ap.parse_args()

    _, hist = train(args.arch, reduced=True, steps=args.steps, batch=8,
                    seq=128, silos=args.silos, local_steps=args.local_steps,
                    non_iid=True, log_path="results/feddcl_llm_pretrain.json")
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"\nloss {first:.3f} -> {last:.3f} over {args.steps} federated steps "
          f"({args.silos} silos, sync every {args.local_steps})")
    assert last < first, "federated training should reduce loss"


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
