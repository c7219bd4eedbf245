"""Batched serving example: requests through the paged continuous-batching
scheduler on a smoke-sized model.

    PYTHONPATH=src python examples/serve_batched.py
"""
from repro.launch import serve as serve_launcher

if __name__ == "__main__":
    serve_launcher.main([
        "--arch", "gemma2-2b", "--smoke", "--requests", "8",
        "--prompt-len", "32", "--new-tokens", "12", "--max-slots", "4",
    ])
