"""End-to-end streaming-serving driver (the flagship example): JIRIAF
control plane + real batched prefill/decode + DBN digital-twin elastic
scaling under the paper's §6.2 pressure trajectory.

    PYTHONPATH=src python examples/serve_stream.py
(args forwarded to repro.launch.serve — e.g. --reduced --controller hpa)
"""
import sys

# serve fixes the host device count from argv when it is imported
sys.argv[1:] = sys.argv[1:] or ["--reduced", "--devices", "8", "--tp", "2",
                                "--nodes", "4", "--ticks", "80"]

from repro.launch import serve  # noqa: E402

if __name__ == "__main__":
    serve.main(sys.argv[1:])
