"""llmserver entrypoint: `python -m kfserving_tpu.predictors.llmserver`.

The generative predictor's standalone server — same CLI convention as
every per-framework server (`--model_name --model_dir --http_port`,
reference pkg/apis/serving/v1beta1/predictor_sklearn.go:77-96 builds
exactly these), serving :predict, :generate, and /generate_stream.
"""

import argparse
import logging

from kfserving_tpu import startup
from kfserving_tpu.engine.compile_cache import enable as enable_compile_cache
from kfserving_tpu.predictors.llm import GenerativeModel
from kfserving_tpu.server.app import ModelServer, parser as server_parser

logging.basicConfig(level=logging.INFO)

parser = argparse.ArgumentParser(parents=[server_parser])
parser.add_argument("--model_name", default="model",
                    help="name under which the model is served")
parser.add_argument("--model_dir", required=True,
                    help="model artifact URI (config.json + optional "
                         "checkpoint.msgpack)")
parser.add_argument("--log_url", default=None,
                    help="CloudEvents sink for payload logging")
parser.add_argument("--log_mode", default="all",
                    choices=["all", "request", "response"])
parser.add_argument("--source_uri", default="",
                    help="CloudEvents source attribute")


def build_server(args) -> ModelServer:
    server = ModelServer(
        http_port=args.http_port,
        container_concurrency=getattr(args, "container_concurrency", 0),
        grpc_port=getattr(args, "grpc_port", None))
    if args.log_url:
        from kfserving_tpu.agent import RequestLogger

        request_logger = RequestLogger(
            args.log_url, source_uri=args.source_uri,
            log_mode=args.log_mode)
        request_logger.attach(server)
        server.services.append(request_logger)
    return server


if __name__ == "__main__":
    import os

    args, _ = parser.parse_known_args()
    enable_compile_cache()
    server = build_server(args)
    model = GenerativeModel(args.model_name, args.model_dir)

    def load():
        startup.report_device()  # first touch of the device
        model.load()
        return model

    if os.environ.get("KFS_STANDBY"):
        # Recycle fast-swap: load (device init + compile) deferred to
        # POST /standby/activate — see jaxserver/__main__.py.
        server.standby_model(load)
        server.start([])
    else:
        server.start([load()])
